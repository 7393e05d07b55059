"""The trace reducer: busy, idle, kernel and per-step times from the
neutral trace form, on a hand-made trace with known answers and on a
small slice of a trace recorded on a TPU v5e. CPU only."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace as TR  # noqa: E402

MS = 1_000_000
RECORDED = BENCH / "tests" / "data" / "trace-v5e-qwen-slice.json"


def handmade():
    # window 0..100 ms; an admission step 10..40 ms, a decode step 50..90
    host = [["trace-window", 0, 100 * MS], ["step:7", 10 * MS, 30 * MS],
            ["step:8", 50 * MS, 40 * MS], ["arrival-wait", 0, 10 * MS],
            ["record", 90 * MS, 5 * MS]]
    dev = [["fusion.1", 12 * MS, 10 * MS],              # prefill work
           ["packed_xnor_gemv.3", 22 * MS, 5 * MS],
           ["fusion.9", 30 * MS, 1 * MS],
           ["while.5", 55 * MS, 21 * MS],               # the decode loop
           ["fusion.2", 55 * MS, 10 * MS],              # ... and its body
           ["packed_xnor_gemv.3", 65 * MS, 4 * MS],
           ["paged_flash_decode.1", 70 * MS, 6 * MS],
           ["copy.1", 95 * MS, 10 * MS]]                # runs past the window
    data = {"window": [0, 100 * MS], "host": host,
            "devices": {"/device:TPU:0": TR.clip(dev, 0, 100 * MS)}}
    steps = {7: {"kind": "admit"}, 8: {"kind": "decode"}}
    return TR.View(data, steps)


def test_busy_and_idle():
    v = handmade()
    # busy: 12..27, 30..31, 55..76, 95..100 = 15 + 1 + 21 + 5 ms
    assert v.busy_s() == pytest.approx(0.042)
    assert v.window_s == pytest.approx(0.1)


def test_busy_inside_steps_of_a_kind():
    v = handmade()
    assert v.busy_in_s("admit") == pytest.approx(0.016)
    assert v.busy_in_s("decode") == pytest.approx(0.021)
    assert [s["kind"] for s in v.steps_of("decode")] == ["decode"]


def test_kernel_time_by_name():
    v = handmade()
    assert v.kernel_s(("packed_xnor_gemv",)) == pytest.approx(0.009)
    assert v.kernel_s(("packed_xnor_gemv",), "decode") == pytest.approx(0.004)
    assert v.kernel_s(("paged_flash_decode",), "decode") == \
        pytest.approx(0.006)
    assert v.kernel_s(("xnor_gemv",)) == 0.0        # base names, not parts


def test_top_ops_and_idle_gaps():
    v = handmade()
    top = dict(v.top_ops())
    assert top["packed_xnor_gemv.3"] == pytest.approx(0.009)
    assert "while.5" not in top                 # a loop holds its body
    gaps = v.idle_gaps()
    assert gaps == [["step", pytest.approx(0.024)],       # 31..55 ms
                    ["step", pytest.approx(0.019)],       # 76..95 ms
                    ["arrival-wait", pytest.approx(0.012)],   # 0..12 ms
                    ["step", pytest.approx(0.003)]]       # 27..30 ms
    assert sum(g for _, g in gaps) == pytest.approx(0.1 - 0.042)


def test_union_and_cover():
    assert TR.union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert TR.covered([(0, 10)], [(2, 3), (5, 20)]) == 6


def test_op_names_from_instruction_text():
    # the profiler names an event by the instruction's whole text, whose
    # operands name the kernel that fed it: only the part before " = "
    # names the event
    text = json.loads(RECORDED.read_text())["example_event_text"]
    assert "paged_prefix_attention" in text
    assert TR.op_name(text) == "fusion.52"
    assert TR.base_name("packed_xnor_gemv.82") == "packed_xnor_gemv"
    assert TR.base_name("copy-start") == "copy-start"


def _mask(events, lo, hi, step=100):
    """Busy mask of ``events`` on a grid of ``step`` ns, by brute force."""
    m = np.zeros((hi - lo) // step, bool)
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            m[(a - lo) // step:-(-(b - lo) // step)] = True
    return m


def test_recorded_v5e_slice():
    data = json.loads(RECORDED.read_text())
    steps = {int(n): {"kind": k} for n, k in data["steps"].items()}
    v = TR.View(data, steps)
    ev = data["devices"]["/device:TPU:0"]
    lo, hi = data["window"]
    grid = 100                                  # ns; edges round to it
    slack = 2 * grid * len(ev) * 1e-9
    busy = _mask(ev, lo, hi, grid).sum() * grid * 1e-9
    assert v.busy_s() == pytest.approx(busy, abs=slack)
    assert 0.0 < v.busy_s() < v.window_s
    # only step:32 lies wholly inside the slice: an admission
    assert [s["kind"] for _, _, s in v.step_spans] == ["admit"]
    a, b = [(s, s + d) for n, s, d in data["host"] if n == "step:32"][0]
    assert v.busy_in_s("admit") == pytest.approx(
        _mask(ev, a, b, grid).sum() * grid * 1e-9, abs=slack)
    # kernels run one at a time on a device: their time is their sum
    for name in ("packed_xnor_gemv", "paged_flash_decode",
                 "paged_prefix_attention"):
        mine = [e for e in ev if TR.base_name(e[0]) == name]
        assert mine, name
        assert v.kernel_s((name,)) == pytest.approx(
            sum(d for _, _, d in mine) * 1e-9, rel=1e-9)
    # the admission ran the prefix-tail kernel, the decode the decode one
    assert v.kernel_s(("paged_prefix_attention",), "admit") > 0
    assert v.kernel_s(("paged_flash_decode",), "admit") == 0
    gaps = v.idle_gaps()
    assert len(gaps) == 10 and all(g > 0 for _, g in gaps)
    assert {label for label, _ in gaps} <= {"step", "record", "submit",
                                            "arrival-wait", "outside-spans"}
    top = v.top_ops()
    assert len(top) == 10 and not any(n.startswith("while") for n, _ in top)


# -- four chips: each chip's own work over that chip's time ------------------

FOUR = BENCH / "tests" / "data" / "trace-4chip-synthetic.json"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _four_chip_run(chips=4):
    import family
    import harness

    data = json.loads(FOUR.read_text())
    steps = {int(n): s for n, s in data["steps"].items()}
    data["devices"] = {d: TR.clip(ev, *data["window"])
                       for d, ev in data["devices"].items()}
    cfg = json.loads((BENCH / "configs" / "qwen2.5-14b.json").read_text())
    serve = dict(cfg["serve"], lanes=16, segment=4)
    return harness.Run(view=TR.View(data, steps), steps=steps, config=cfg,
                       serve=serve, peak=PEAK, chips=chips,
                       family=family.of(cfg))


def _read(name, run):
    import harness

    return harness.reader(name)(run)


def _max(ops, nbytes):
    return max(ops / PEAK["bf16_flops"], nbytes / PEAK["hbm_bytes_per_s"])


def test_four_chip_busy_and_kernels_average_over_chips():
    v = _four_chip_run().view
    # per step, chip d is busy 10+d + 2 + 1 + 2+d + 5 ms
    busy = [2 * (20 + 2 * d) for d in range(4)]
    assert v.busy_in_s("decode") == pytest.approx(sum(busy) / 4 * 1e-3)
    assert v.kernel_s(("packed_xnor_gemv",), "decode") == \
        pytest.approx(2 * 11.5e-3)
    assert v.ops_s(TR.is_collective, "decode") == pytest.approx(
        2 * (1 + 3.5) * 1e-3)
    assert TR.is_collective("all-gather-done.3")
    assert TR.is_collective("all-gather.7") and TR.is_collective("all-reduce")
    assert not TR.is_collective("all-gather-fusion.2")
    assert not TR.is_collective("fusion.4")


def test_gemv_roofline_counts_each_chips_columns():
    """A chip's least time is a quarter of the published work, whatever
    the program splits or replicates, so replication shows as waste."""
    run = _four_chip_run()
    d, hd, L, lanes = 5120, 128, 48, 16
    q, kv, ff = 40 * hd, 8 * hd, 13824

    def cost(k, n):          # ops and needed bytes of one (16, k) x (k, n)
        return 2.0 * lanes * k * n, k // 32 * n * 4 + lanes * k * 2 \
            + lanes * n * 4

    whole = [(d, q), (d, kv), (d, kv), (q, d), (d, 2 * ff), (ff, d)]
    least = 2 * 4 * L * sum(_max(*cost(k, n)) for k, n in whole) / 4
    got = _read("gemv_roofline", run)
    assert got == pytest.approx(100 * least / (2 * 11.5e-3), rel=1e-12)
    assert 0 < got <= 100
    # the same kernel time on one chip reads four times the share
    one = _read("gemv_roofline", _four_chip_run(chips=1))
    assert one == pytest.approx(4 * got, rel=1e-12)


def test_paged_decode_roofline_counts_each_chips_kv_heads():
    run = _four_chip_run()
    least = 0.0
    for ctxs in ([1000, 3000], [1004, 3004]):
        for j in range(4):
            # a chip holds 2 of the 8 KV heads and 10 of the 40 query heads
            ops = sum(2.0 * 2 * (c + j) * 10 * 128 for c in ctxs)
            nbytes = sum(2 * (c + j) * 2 * 128 * 2 + 10 * 128 * 6
                         for c in ctxs)
            least += _max(ops, nbytes)
    least *= 48
    got = _read("paged_decode_roofline", run)
    assert got == pytest.approx(100 * least / 4e-3, rel=1e-12)
    assert 0 < got <= 100
    # counted as if one chip held all 8 KV heads it would pass 100%
    assert 4 * got > 100


def test_decode_mfu_divides_by_every_chips_time():
    run = _four_chip_run()
    d, hd, L, V = 5120, 128, 48, 152064
    q, kv, ff = 40 * hd, 8 * hd, 13824
    proj = d * q + 2 * d * kv + q * d + d * 2 * ff + ff * d
    ops = sum(2.0 * (L * proj + d * V + L * 2 * (c + j) * 40 * hd)
              for ctxs in ([1000, 3000], [1004, 3004]) for c in ctxs
              for j in range(4))
    busy = sum(2 * (20 + 2 * d) for d in range(4)) / 4 * 1e-3
    got = _read("decode_mfu", run)
    assert got == pytest.approx(100 * ops / (busy * 197e12 * 4), rel=1e-12)
    assert 0 < got <= 100
    one = _read("decode_mfu", _four_chip_run(chips=1))
    assert one == pytest.approx(4 * got)


def test_collective_share_reads_the_all_gather():
    got = _read("collective_share", _four_chip_run())
    busy = sum(2 * (20 + 2 * d) for d in range(4)) / 4
    assert got == pytest.approx(100 * 2 * 4.5 / busy)
    assert _read("collective_share", _four_chip_run(chips=1)) is None
