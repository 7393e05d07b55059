"""decode_mfu: the model operations of the decode steps in the traced
slice over their device time times the chip's bf16 peak, in percent.

Operations are 2 per multiply-add, per live lane and step: every Boolean
projection at its published width (40 query heads for qwen2.5-14b, not the
48 the program pads to), the LM head, and attention (scores and values)
over the lane's live context. Idle lanes the program computes anyway do
not count."""


def projections(cfg: dict) -> list:
    """(fan-in, fan-out) of each projection of one layer, published."""
    d = cfg["hidden_size"]
    fam = cfg["program"]["family"]
    if fam == "dense":
        hd = cfg["program"].get("head_dim", d // cfg["num_attention_heads"])
        q = cfg["num_attention_heads"] * hd
        kv = cfg["num_key_value_heads"] * hd
        ff = cfg["intermediate_size"]
        return [(d, q), (d, kv), (d, kv), (q, d), (d, 2 * ff), (ff, d)]
    if fam == "ssm":
        di, n, r = (cfg["intermediate_size"], cfg["state_size"],
                    cfg["time_step_rank"])
        return [(d, di), (d, di), (di, r + 2 * n), (r, di), (di, d)]
    raise ValueError(fam)


def token_ops(cfg: dict, context: int) -> float:
    """Operations to decode one token at ``context`` positions."""
    layers = cfg["num_hidden_layers"]
    macs = layers * sum(k * n for k, n in projections(cfg))
    macs += cfg["hidden_size"] * cfg["vocab_size"]
    if cfg["program"]["family"] == "dense":
        hd = cfg["program"].get("head_dim",
                                cfg["hidden_size"] // cfg["num_attention_heads"])
        macs += layers * 2 * context * cfg["num_attention_heads"] * hd
    return 2.0 * macs


def read(run):
    segs = run.view.steps_of("decode")
    busy = run.view.busy_in_s("decode")
    if not segs or busy <= 0:
        return None
    seg = run.serve["segment"]
    ops = sum(token_ops(run.config, c + j)
              for s in segs for c in s["contexts"] for j in range(seg))
    return 100.0 * ops / (busy * run.peak["bf16_flops"])
