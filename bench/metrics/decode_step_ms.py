"""decode_step_ms: device time of the decode-segment steps in the traced
slice over the decode steps they ran (segments times segment length)."""


def read(run):
    segs = run.view.steps_of("decode")
    busy = run.view.busy_in_s("decode")
    if not segs or busy <= 0:
        return None
    return busy * 1e3 / (len(segs) * run.serve["segment"])
