"""gemv_roofline: share of the packed-XNOR GEMV's roofline reached in the
decode steps of the traced slice, in percent: the least time the chip
could take for those calls over the time the kernel took.

Each call's least time is the larger of its operations over the bf16 peak
and its bytes over the HBM bandwidth. Bytes are what the algorithm needs
at the published widths: the packed weight words, the lanes' activations
in bfloat16, and float32 outputs; padded tiles and padded heads do not
count, so padding waste shows. Kernel events are found by name."""

import importlib.util
from pathlib import Path

NAMES = ("packed_xnor_gemv",)    # the kernel's instruction in the device trace


def _projections(cfg):
    path = Path(__file__).with_name("decode_mfu.py")
    spec = importlib.util.spec_from_file_location("_decode_mfu", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.projections(cfg)


def call_cost(m: int, k: int, n: int):
    """(operations, bytes) of one (m, k) x (k, n) packed GEMV."""
    ops = 2.0 * m * k * n
    nbytes = (-(-k // 32)) * n * 4 + m * k * 2 + m * n * 4
    return ops, nbytes


def step_least_s(cfg: dict, lanes: int, peak: dict) -> float:
    t = 0.0
    for k, n in _projections(cfg):
        ops, nbytes = call_cost(lanes, k, n)
        t += max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
    return t * cfg["num_hidden_layers"]


def read(run):
    segs = run.view.steps_of("decode")
    took = run.view.kernel_s(NAMES, "decode")
    if not segs or took <= 0:
        return None
    steps = len(segs) * run.serve["segment"]
    least = steps * step_least_s(run.config, run.serve["lanes"], run.peak)
    return 100.0 * least / took
