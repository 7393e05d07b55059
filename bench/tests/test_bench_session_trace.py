"""The session's own spans and the device's program events: the readers
on a hand-made trace with known answers, idle gaps labelled by program
spans, a tiny session profiled on the CPU, and the shared clock on a slice
recorded on a TPU v5e. CPU only."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import session_trace as ST  # noqa: E402
import trace as TR  # noqa: E402

MS = 1_000_000
RECORDED = BENCH / "tests" / "data" / "trace-v5e-qwen-program-slice.json"


def span(name, a, b, **meta):
    return [name, a * MS, (b - a) * MS, meta]


def handmade() -> dict:
    """Window 0..200 ms: a cold admission (rid 1), an exact hit (rid 2),
    two decode segments back to back, a tail admission (rid 3), a third
    segment after it, and a prefill program that runs past the window."""
    program = [
        span("serve.schedule", 10, 11),
        span("serve.prefill", 11, 15, rid=1, tokens=100, hit=0),
        span("serve.first_token", 15, 40, rid=1),
        span("serve.hit_admit", 41, 43, rid=2),
        span("serve.first_token", 43, 50, rid=2),
        span("serve.segment", 60, 62),
        span("serve.segment_wait", 62, 100),
        span("serve.drain", 100, 101),
        span("serve.segment", 103, 105),
        span("serve.segment_wait", 105, 140),
        span("serve.prefill", 150, 152, rid=3, tokens=50, hit=16),
        span("serve.first_token", 152, 180, rid=3),
        span("serve.segment", 180, 181),
        span("serve.segment_wait", 181, 189),
    ]
    modules = {"serve_prefill": [[13 * MS, 22 * MS], [195 * MS, 15 * MS]],
               "serve_hit_admit": [[42 * MS, 3 * MS]],
               "serve_decode_segment": [[61 * MS, 37 * MS],
                                        [104 * MS, 34 * MS],
                                        [181 * MS, 8 * MS]],
               "serve_tail_prefill": [[151 * MS, 24 * MS]],
               "fold_in": [[36 * MS, 2 * MS]]}
    ops = [(13, 20), (22, 35), (36, 38), (42, 45), (61, 98), (104, 138),
           (151, 160), (165, 175), (176, 178), (181, 189), (195, 210)]
    dev = TR.clip([[f"op.{i}", a * MS, (b - a) * MS]
                   for i, (a, b) in enumerate(ops)], 0, 200 * MS)
    host = [["trace-window", 0, 200 * MS], ["step:1", 9 * MS, 41 * MS],
            ["step:2", 59 * MS, 43 * MS], ["step:3", 103 * MS, 38 * MS],
            ["step:4", 149 * MS, 32 * MS], ["record", 189 * MS, 3 * MS]]
    return {"window": [0, 200 * MS], "host": host,
            "devices": {"/device:TPU:0": dev},
            "program": program, "modules": modules}


def test_prefill_program_time_per_ktok():
    # programs wholly in the slice: rid 1's (busy 7 + 13 ms) and rid 3's
    # tail (9 + 10 ms), 150 tokens; the one past the window is left out
    assert ST.prefill_program_ms_per_ktok(handmade()) == \
        pytest.approx(39.0 / 0.150)


def test_admit_gap():
    # rid 1: 11..40, busy 7 + 13 + 2 -> idle 7; rid 2: 41..50, busy 3 ->
    # idle 6; rid 3: 150..180, busy 9 + 10 + 2 -> idle 9
    assert ST.admit_gap_ms(handmade()) == pytest.approx(22.0 / 3)


def test_segment_gap():
    # only the first two segments follow each other with no serve program
    # between them (the third follows the tail prefill): 98..104, idle
    assert ST.segment_gap_ms(handmade()) == pytest.approx(6.0)


def test_queue_wait():
    c0 = {"hit_tokens": 5, "queue_wait_ms": 100.0, "queue_waited": 3}
    c1 = {"hit_tokens": 9, "queue_wait_ms": 460.0, "queue_waited": 7}
    assert ST.queue_wait_ms(c0, c1) == pytest.approx(90.0)
    assert ST.queue_wait_ms(c0, dict(c1, queue_waited=3)) is None
    assert ST.queue_wait_ms({"hit_tokens": 5}, {"hit_tokens": 9}) is None


def test_readers_find_nothing_without_program_spans():
    data = dict(handmade(), program=[], modules={})
    for read in (ST.prefill_program_ms_per_ktok, ST.admit_gap_ms,
                 ST.segment_gap_ms):
        assert read(data) is None


def test_idle_gaps_labelled_by_program_spans():
    gaps = ST.idle_gaps(handmade())
    assert gaps == [
        ["serve.first_token", pytest.approx(0.016)],   # 45..61
        ["serve.prefill", pytest.approx(0.013)],       # 0..13
        ["serve.segment_wait", pytest.approx(0.013)],  # 138..151
        ["serve.segment_wait", pytest.approx(0.006)],  # 98..104
        ["record", pytest.approx(0.006)],              # 189..195: no span
        ["serve.first_token", pytest.approx(0.005)],   # 160..165
        ["serve.first_token", pytest.approx(0.004)],   # 38..42
        ["serve.first_token", pytest.approx(0.003)],   # 178..181
        ["serve.first_token", pytest.approx(0.002)],   # 20..22
        ["serve.first_token", pytest.approx(0.001)]]   # 35..36


def test_idle_gaps_without_program_spans_are_the_harness_labels():
    data = dict(handmade(), program=[])
    steps = {n: {"kind": "admit"} for n in range(1, 5)}
    assert ST.idle_gaps(data) == TR.View(data, steps).idle_gaps()


def test_idle_gap_goes_to_the_innermost_of_equal_covers():
    data = {"window": [0, 100 * MS], "host": [],
            "devices": {"/device:TPU:0": [["op.1", 20 * MS, 80 * MS]]},
            "program": [span("serve.segment", 0, 100),
                        span("serve.segment_wait", 0, 20)]}
    assert ST.idle_gaps(data) == [["serve.segment_wait",
                                   pytest.approx(0.02)]]


def test_module_base_names():
    assert ST.module_base("jit_serve_decode_segment(1234567)") == \
        "serve_decode_segment"
    assert ST.module_base("jit_serve_prefill") == "serve_prefill"
    assert ST.module_base("jit_fn(42)") == "fn"


def test_a_profiled_session_shows_every_span(tmp_path):
    import jax
    import numpy as np
    from repro.configs import get_smoke
    from repro.models import lm_init
    from repro.serve import SamplingParams, ServeEngine

    cfg = get_smoke("qwen2.5-14b")
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_len=64, packed=True,
                         prefix_cache=True)
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab_size, 18).astype(np.int32)
    cold = np.concatenate([shared, [5, 6, 7]]).astype(np.int32)
    tail = np.concatenate([shared, [9, 10, 11, 12, 13]]).astype(np.int32)
    sess = engine.session(lanes=2, page_size=4, segment=2,
                          buckets=(8, 16, 32, 64))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("trace-window"):
        for wave in ([cold], [tail, cold]):   # rids 0; 1 (tail), 2 (exact)
            for p in wave:
                sess.submit(p, SamplingParams(max_tokens=3))
            sess.run_until_idle()
    jax.profiler.stop_trace()
    sess.close()
    data = TR.load_profile(str(tmp_path))
    data.update(ST.load(str(tmp_path), *data["window"]))
    prog = data["program"]
    assert {p[0] for p in prog} == {
        "serve.schedule", "serve.prefill", "serve.hit_admit",
        "serve.first_token", "serve.segment", "serve.segment_wait",
        "serve.drain"}
    prefill = {p[3]["rid"]: p[3] for p in prog if p[0] == "serve.prefill"}
    assert prefill[0] == {"rid": 0, "tokens": cold.size, "hit": 0}
    assert prefill[1]["hit"] > 0 and prefill[1]["hit"] % 4 == 0
    assert prefill[1]["tokens"] + prefill[1]["hit"] == tail.size
    assert [p[3] for p in prog if p[0] == "serve.hit_admit"] == [{"rid": 2}]
    assert sorted(p[3]["rid"] for p in prog
                  if p[0] == "serve.first_token") == [0, 1, 2]
    for p in prog:                      # step-level spans carry nothing
        if p[0] in ("serve.schedule", "serve.segment", "serve.segment_wait",
                    "serve.drain"):
            assert p[3] == {}


def _recorded():
    return json.loads(RECORDED.read_text())


def test_recorded_prefill_programs_lie_inside_their_admission():
    """Host spans and device programs share the profiler's clock: each
    prefill program starts after its request's ``serve.prefill`` span
    starts and ends before that request's first token reached the host."""
    data = _recorded()
    prog = data["program"]
    mods = sorted((s, s + d) for base in ST.PREFILL_PROGRAMS
                  for s, d in data["modules"].get(base, []))
    assert mods
    for a, b in mods:
        owner = [p for p in prog if p[0] == "serve.prefill" and p[1] <= a]
        assert owner, (a, b)
        rid = owner[-1][3]["rid"]
        first = [p for p in prog if p[0] == "serve.first_token"
                 and p[3]["rid"] == rid and p[1] >= owner[-1][1]]
        assert first and b <= first[0][1] + first[0][2], (rid, a, b)


def test_recorded_segments_start_inside_a_segment_dispatch():
    data = _recorded()
    starts = sorted(p[1] for p in data["program"]
                    if p[0] == "serve.segment")
    segs = data["modules"][ST.SEGMENT_PROGRAM]
    assert len(segs) >= 2
    for s, d in segs:
        before = [a for a in starts if a <= s]
        assert before, s
        # the program the latest dispatch queued: no other dispatch between
        assert s - before[-1] < 1e9


def test_recorded_slice_reads_what_the_chip_run_read():
    # the slice keeps the device's busy intervals, not its operations, so
    # the readers give the numbers of the run it was cut from
    data = _recorded()
    assert ST.prefill_program_ms_per_ktok(data) == pytest.approx(575.897,
                                                                rel=1e-5)
    assert ST.admit_gap_ms(data) == pytest.approx(11.6292, rel=1e-4)
    assert ST.segment_gap_ms(data) == pytest.approx(5.3122, rel=1e-4)
    gaps = ST.idle_gaps(data)
    assert len(gaps) == 10
    assert all(label.startswith("serve.") for label, _ in gaps)
