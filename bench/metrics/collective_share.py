"""collective_share: device time in collective operations (all-gather,
all-reduce, reduce-scatter, collective-permute, all-to-all, found by base
name: ``trace.is_collective``) inside the decode steps of the traced slice
over device busy time inside them, in percent, each averaged over the
chips. On a serve mesh that is the all-gather of the attention heads
before the replicated o-projection. Nothing to read on one chip, or where
no collective ran."""

import trace as TR


def read(run):
    if run.chips < 2:
        return None
    busy = run.view.busy_in_s("decode")
    coll = run.view.ops_s(TR.is_collective, "decode")
    if busy <= 0 or coll <= 0:
        return None
    return 100.0 * coll / busy
