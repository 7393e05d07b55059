"""gemv_roofline: share of the packed-XNOR GEMV's roofline reached in the
decode steps of the traced slice, in percent: the least time one chip
could take for its calls over the time the kernel took on it, averaged
over the chips.

Each call's least time is the larger of its operations over the bf16 peak
and its bytes over the HBM bandwidth. Bytes are what the algorithm needs
at the published widths (the family's ``decode_work``): the packed weight
words, the lanes' activations in bfloat16, and float32 outputs; padded
tiles and padded heads do not count, so padding waste shows. On a serve
mesh of n chips a chip's least time is the step's over n, an ideal split
of the published work, whatever the program splits or replicates: a
projection every chip computes whole shows as waste. Kernel events are
found by name."""

NAMES = ("packed_xnor_gemv",)    # the kernel's instruction in the device trace


def call_cost(m: int, k: int, n: int):
    """(operations, bytes) of one (m, k) x (k, n) packed GEMV."""
    ops = 2.0 * m * k * n
    nbytes = (-(-k // 32)) * n * 4 + m * k * 2 + m * n * 4
    return ops, nbytes


def step_least_s(work: dict, lanes: int, peak: dict) -> float:
    """The least time of one decode step's GEMVs on one chip."""
    per_call = {}                 # calls per token -> least time of one each
    for k, n, calls in work["projections"]:
        ops, nbytes = call_cost(lanes, k, n)
        per_call[calls] = per_call.get(calls, 0.0) + max(
            ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
    return sum(t * calls for calls, t in per_call.items())


def read(run):
    segs = run.view.steps_of("decode")
    took = run.view.kernel_s(NAMES, "decode")
    if not segs or took <= 0:
        return None
    steps = len(segs) * run.serve["segment"]
    work = run.family.decode_work(run.config)
    least = steps * step_least_s(work, run.serve["lanes"],
                                 run.peak) / run.chips
    return 100.0 * least / took
