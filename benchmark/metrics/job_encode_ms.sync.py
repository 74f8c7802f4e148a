"""job_encode_ms.sync: host milliseconds per observation in the window of
`bls.job.encode`: a job's host prep in `begin_job` beside the message
cache (sort by root, index/mask and signature planes, message-plane
encode, host-to-device copies).

Read from `lodestar_bls_job_host_step_seconds{step="encode"}` on the
verifier's registry, sampled around the window; None where the program
has no such series or the window observed no such step."""

NAME = "job_encode_ms.sync"
STEP = "encode"


def sample(node):
    hist = node["registry"].get("lodestar_bls_job_host_step_seconds")
    return None if hist is None else (hist.sum(STEP), hist.count(STEP))


def read(ctx):
    before, after = ctx["samples"].get(NAME, (None, None))
    if before is None or after is None or after[1] == before[1]:
        return None
    return 1000.0 * (after[0] - before[0]) / (after[1] - before[1])
