"""msg_field_ms.sync: host milliseconds per observation in the window of
`bls.msg.field`: the message cache's hash-to-field, sgn0 and plane
encoding of a job's missed signing roots, padding lanes included.

Read from `lodestar_bls_job_host_step_seconds{step="field"}` on the
verifier's registry, sampled around the window; None where the program
has no such series or the window observed no such step."""

NAME = "msg_field_ms.sync"
STEP = "field"


def sample(node):
    hist = node["registry"].get("lodestar_bls_job_host_step_seconds")
    return None if hist is None else (hist.sum(STEP), hist.count(STEP))


def read(ctx):
    before, after = ctx["samples"].get(NAME, (None, None))
    if before is None or after is None or after[1] == before[1]:
        return None
    return 1000.0 * (after[0] - before[0]) / (after[1] - before[1])
