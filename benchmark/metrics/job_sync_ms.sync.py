"""job_sync_ms.sync: milliseconds per verifier job that the host spent
blocked in `finish_job` (`lodestar_bls_verify_seconds{phase="device"}`):
the wait for the device's verdicts, not the device's busy time."""


def read(ctx):
    c = ctx["counters"]
    n = c.get("verify_device_count")
    return 1000.0 * c["verify_device_sum"] / n if n else None
