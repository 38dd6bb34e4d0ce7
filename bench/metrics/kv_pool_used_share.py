"""KV cache: the block pool's high-water mark over its capacity, %
(``pool_high_water_blocks`` / ``pool_blocks`` of ``Scheduler.summary()``,
over set-up and window after the warm-up's reset). Today every fused step
decodes the whole pool, so the share that is live is the share of that
work that serves a token."""


def read(r):
    total = r.summary.get("pool_blocks")
    if not total:
        return None
    return r.summary["pool_high_water_blocks"] / total * 100.0
