"""job_host_ms.sync: host milliseconds per verifier job in the window
(`lodestar_bls_verify_seconds{phase="host"}`: plane encoding, the
signing-root hash, padding and dispatch in `begin_job`)."""


def read(ctx):
    c = ctx["counters"]
    n = c.get("verify_host_count")
    return 1000.0 * c["verify_host_sum"] / n if n else None
