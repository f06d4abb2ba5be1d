"""KV manager: the most of the pool in use at the end of any step,
1 - min(kv_blocks_free) / pool blocks. %."""


def read(run):
    free = [r.kv_blocks_free for r in run.steps if r.kv_blocks_free is not None]
    if not free or not run.pool_blocks:
        return None
    return 100.0 * (1.0 - min(free) / run.pool_blocks)
