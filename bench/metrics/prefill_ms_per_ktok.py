"""prefill_ms_per_ktok: device time of the admission steps in the traced
slice (prefill programs, the hit-admission path and first-token sampling:
an admission step ends when its first token reaches the host) per 1,000
prompt tokens those steps actually prefilled (prompt minus prefix-cache
hits). Steps are told apart by the harness's own spans."""


def read(run):
    steps = run.view.steps_of("admit")
    tokens = sum(s["prompt_tokens"] - s["hit_tokens"] for s in steps)
    busy = run.view.busy_in_s("admit")
    if tokens <= 0 or busy <= 0:
        return None
    return busy * 1e3 / (tokens / 1e3)
