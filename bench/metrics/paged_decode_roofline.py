"""paged_decode_roofline: share of the paged decode-attention kernel's
roofline reached in the decode steps of the traced slice, in percent: one
chip's least time over the kernel's time on it, averaged over the chips.

Per step and layer (one kernel call) the least time is the larger of the
operations (scores and values over each live lane's context, at the
published query heads) over the bf16 peak and the bytes over the HBM
bandwidth: the bfloat16 K and V rows of each live lane's context, its
query and its float32 output. On a serve mesh of n chips a chip's least
time is an ideal split: 1/n of the KV heads and of the query heads (2 of
qwen2.5-14b's 8 KV heads on four chips).
Live context comes from the harness's own records (prompt length plus
tokens emitted), not from the program. Nothing to read for a model
without attention (the family's ``decode_work``)."""

NAMES = ("paged_flash_decode",)  # the kernel's instruction in the device trace


def lane_cost(att: dict, context: int, chips: int = 1):
    """(operations, bytes) of one lane's decode attention in one layer, on
    one chip of ``chips``."""
    h = att["heads"] / chips
    hd = att["head_dim"]
    kv = att["kv_heads"] / chips
    ops = 2.0 * 2 * context * h * hd
    nbytes = 2 * context * kv * hd * 2 + h * hd * 2 + h * hd * 4
    return ops, nbytes


def read(run):
    att = run.family.decode_work(run.config)["attention"]
    if att is None:
        return None
    segs = run.view.steps_of("decode")
    took = run.view.kernel_s(NAMES, "decode")
    if not segs or took <= 0:
        return None
    seg, peak = run.serve["segment"], run.peak
    least = 0.0
    for s in segs:
        for j in range(seg):            # one kernel call per step and layer
            cost = [lane_cost(att, c + j, run.chips) for c in s["contexts"]]
            least += max(sum(o for o, _ in cost) / peak["bf16_flops"],
                         sum(b for _, b in cost) / peak["hbm_bytes_per_s"])
    least *= att["layers"]
    return 100.0 * least / took
