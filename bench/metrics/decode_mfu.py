"""decode_mfu: the model operations of the decode steps in the traced
slice over their device time on every chip of the cell times the chip's
bf16 peak, in percent.

Operations are 2 per multiply-add, per live lane and step: every Boolean
projection at its published width (the family's ``decode_work``: 40 query
heads for qwen2.5-14b, not the 48 the program pads to), the LM head, and
attention (scores and values) over the lane's live context. Idle lanes
the program computes anyway do not count, nor does work a serve mesh
repeats on every chip: the device time is the busy time averaged over the
chips times their number, so one chip reads as it always has."""


def token_ops(cfg: dict, work: dict, context: int) -> float:
    """Operations to decode one token at ``context`` positions."""
    macs = sum(k * n * calls for k, n, calls in work["projections"])
    macs += cfg["hidden_size"] * cfg["vocab_size"]
    att = work["attention"]
    if att is not None:
        macs += att["layers"] * 2 * context * att["heads"] * att["head_dim"]
    return 2.0 * macs


def read(run):
    segs = run.view.steps_of("decode")
    busy = run.view.busy_in_s("decode")
    if not segs or busy <= 0:
        return None
    seg = run.serve["segment"]
    work = run.family.decode_work(run.config)
    ops = sum(token_ops(run.config, work, c + j)
              for s in segs for c in s["contexts"] for j in range(seg))
    return 100.0 * ops / (busy * run.peak["bf16_flops"] * run.chips)
