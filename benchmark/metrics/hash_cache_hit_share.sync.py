"""hash_cache_hit_share.sync: share of signing-root lookups in the
window that the verifier's hashed-message cache answered (%); misses
are hashed to G2 on the device."""


def read(ctx):
    c = ctx["counters"]
    hits, misses = c.get("msg_cache_hits"), c.get("msg_cache_misses")
    if hits is None or not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
