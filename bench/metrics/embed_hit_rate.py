"""embed_hit_rate (tiers): fast-tier reads over all reads of the
``embeddings`` resource, from the tier counters' change over the window,
in percent."""


def read(ctx):
    row = ctx.tier_delta.get("embeddings")
    if not row:
        return None
    total = row["fast_reads"] + row["slow_reads"]
    return 100.0 * row["fast_reads"] / total if total else None
