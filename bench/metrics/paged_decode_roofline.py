"""paged_decode_roofline: share of the paged decode-attention kernel's
roofline reached in the decode steps of the traced slice, in percent.

Per step and layer (one kernel call) the least time is the larger of the
operations (scores and values over each live lane's context, 40 heads)
over the bf16 peak and the bytes over the HBM bandwidth: the bfloat16 K and V rows of each live
lane's context, its query and its float32 output. Live context comes from
the harness's own records (prompt length plus tokens emitted), not from
the program. Nothing to read for a model without attention."""

NAMES = ("paged_flash_decode",)  # the kernel's instruction in the device trace


def lane_cost(cfg: dict, context: int):
    """(operations, bytes) of one lane's decode attention in one layer."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg["program"].get("head_dim", d // h)
    kv = cfg["num_key_value_heads"]
    ops = 2.0 * 2 * context * h * hd
    nbytes = 2 * context * kv * hd * 2 + h * hd * 2 + h * hd * 4
    return ops, nbytes


def read(run):
    if run.config["program"]["family"] != "dense":
        return None
    segs = run.view.steps_of("decode")
    took = run.view.kernel_s(NAMES, "decode")
    if not segs or took <= 0:
        return None
    seg, peak = run.serve["segment"], run.peak
    least = 0.0
    for s in segs:
        for j in range(seg):            # one kernel call per step and layer
            cost = [lane_cost(run.config, c + j) for c in s["contexts"]]
            least += max(sum(o for o, _ in cost) / peak["bf16_flops"],
                         sum(b for _, b in cost) / peak["hbm_bytes_per_s"])
    least *= run.config["num_hidden_layers"]
    return 100.0 * least / took
