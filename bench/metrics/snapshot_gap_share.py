"""snapshot_gap_share: share of the prompt tokens the prefix cache matched
over the window that a hit gave up for want of an SSM snapshot, in
percent: the change of ``resume_gap_tokens`` over the change of
``hit_tokens`` plus ``resume_gap_tokens``, from the session's own counters
(``PrefixCache.stats``). A hit resumes from the deepest boundary that holds
a snapshot, and the tail prefill recomputes the matched tokens above it.
Nothing to read where the session has no such counter or matched
nothing."""


def read(run):
    c0, c1 = run.counters
    if "resume_gap_tokens" not in c1:
        return None
    gap = c1["resume_gap_tokens"] - c0.get("resume_gap_tokens", 0)
    matched = gap + c1["hit_tokens"] - c0["hit_tokens"]
    if matched <= 0:
        return None
    return 100.0 * gap / matched
