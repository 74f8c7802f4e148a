"""msg_device_ms.sync: host milliseconds per observation in the window of
`bls.msg.device_hash`: the host blocked on the device hash-to-G2 of a
job's missed signing roots, from its dispatch until its results are on
the host, the wait behind work queued ahead of it included.

Read from `lodestar_bls_job_host_step_seconds{step="device_hash"}` on the
verifier's registry, sampled around the window; None where the program
has no such series or the window observed no such step."""

NAME = "msg_device_ms.sync"
STEP = "device_hash"


def sample(node):
    hist = node["registry"].get("lodestar_bls_job_host_step_seconds")
    return None if hist is None else (hist.sum(STEP), hist.count(STEP))


def read(ctx):
    before, after = ctx["samples"].get(NAME, (None, None))
    if before is None or after is None or after[1] == before[1]:
        return None
    return 1000.0 * (after[0] - before[0]) / (after[1] - before[1])
