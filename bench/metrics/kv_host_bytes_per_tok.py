"""KV bytes that crossed between host and device per output token:
gathers of host-resident blocks, appends to host-resident tail blocks
(there and back) and tier migrations, counted from the pool's block
kinds and its migration counter."""
from bench import stats


def read(run):
    n = stats.tokens_in_window(run)
    if not n:
        return None
    return (run.host_kv_bytes + run.migrated_bytes) / n
