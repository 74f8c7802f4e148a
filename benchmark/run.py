"""One cell of the benchmark, on the chip.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in BENCHMARK.json; its configuration is the
file the configuration entry names, its traffic mix is
benchmark/traffic/<traffic>.json, the traffic's `loop` names the module
benchmark/loops/<loop>.py that generates and offers it and chooses what
is compared, and each of its metrics is read by
benchmark/metrics/<metric>.py.  The run:

1. fails at once, printing no result, unless JAX's devices are TPUs,
   as many as the cell asks for;
2. makes keys, the device pubkey table and the traffic from --seed (the
   signatures in worker processes, while the node warms up);
3. builds the node's BLS path as the node composes it:
   `TpuBlsVerifier(table, metrics=...)` behind `create_bls_service`,
   every default kept;
4. warms up every program the traffic drives (the loop's first call,
   then a few of its requests in flight at once), and counts that as
   set-up;
5. measures for --seconds, with the profiler on when --trace 1;
6. compares the answers with the plain reference (benchmark/check.py)
   once the window has closed and the node is freed;
7. prints the end-to-end metrics (--trace 0) or the per-layer metrics
   (--trace 1) as one JSON object, the last line of standard output;
   the numbers compared, each beside its limit, are the last lines of
   standard error and the last key of that object.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from collections import Counter  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = "benchmark"
DRAIN_S = 60.0  # an answer due in the window may come this late
SIGNING_WORKERS = 3
SETTLE_REQUESTS = 8  # requests run before the window, jobs in flight as in it
TRACE_S = 10.0  # the profiler records at most this much of the window
FIRST_CALL_S = 1100.0  # a cold first block compiles every program


def _log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


# -- the cell, found by name --------------------------------------------------


def load_cell(root: Path, workload: str) -> dict:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no cell {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    e2e = [m for m in spec["end_to_end"] if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [
        m
        for m in spec["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]
    return {
        "cell": cell,
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads((root / HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": e2e,
        "per_layer": layer,
    }


def _module(root: Path, kind: str, name: str):
    path = root / HERE / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {kind} file {path.relative_to(root)}")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, name: str):
    """The metric's module: `read(ctx)` gives its value or None, and an
    optional `sample(node)` is called just before and just after the
    window; its two results are `ctx["samples"][name]`."""
    return _module(root, "metrics", name)


def load_loop(root: Path, name: str):
    """The traffic's loop: `Loop(cfg, traffic, rng, sks)`."""
    return _module(root, "loops", name).Loop


def make_keys(rng, n: int):
    from .reference import bls as RB

    return [RB.keygen(rng.bytes(32)) for _ in range(n)]


# -- what the program counts ----------------------------------------------------


class CompileEvents:
    """JAX's own trace / lower / compile events: counts, and sums of
    their seconds (nested traces count in each enclosing one, so a sum
    can exceed the wall time it spans)."""

    KEYS = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration", "backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = Counter()
        self.count = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_kw):
        for key in self.KEYS:
            if name.endswith(key):
                self.seconds[key] += secs
                self.count[key] += 1

    def split(self) -> dict:
        return {
            "trace": self.seconds[self.KEYS[0]],
            "lower": self.seconds[self.KEYS[1]],
            "compile": self.seconds[self.KEYS[2]],
        }


def counters(verifier, registry) -> dict:
    """Window-relevant counts, read where the program keeps them."""
    from lodestar_tpu.utils.metrics import global_registry

    out = {}
    m = getattr(verifier, "metrics", None)
    vs = getattr(m, "verify_seconds", None)
    if vs is not None:
        for phase in ("host", "device"):
            out[f"verify_{phase}_sum"] = vs.sum(phase)
            out[f"verify_{phase}_count"] = vs.count(phase)
    cache = getattr(verifier, "messages", None)
    if cache is not None:
        out["msg_cache_hits"] = cache.hits
        out["msg_cache_misses"] = cache.misses
    fb = registry.get("lodestar_bls_breaker_host_fallback_sets_total")
    out["host_fallback_sets"] = fb.value if fb is not None else 0
    builds = global_registry().get("lodestar_tpu_pallas_builds_total")
    out["pallas_builds"] = sum(builds.get(k) for k in builds.label_values()) if builds else 0
    return out


def dispatched_shapes(verifier):
    """(entry, n, k) of every program the verifier has dispatched, or
    None for a verifier that does not record them."""
    seen = getattr(verifier, "_dispatched", None)
    if seen is None:
        return None
    out = set()
    for key in seen:
        name, shapes = key[0], [s for s, _ in key[1:]]
        if name == "hash_to_g2":
            out.add((name, shapes[0][1]))
        else:
            out.add((name, shapes[-1][0], shapes[2][1]))
    return out


# -- the node --------------------------------------------------------------------


def tpu_verifier(table, metrics):
    from lodestar_tpu.bls.verifier import TpuBlsVerifier

    return TpuBlsVerifier(table, metrics=metrics)


def annotate(obj, attr: str, name: str) -> None:
    """Wrap obj.attr in a profiler span, so that the trace can say what
    the host was doing while the device idled."""
    import jax

    fn = getattr(obj, attr, None)
    if fn is None:
        return

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)

    setattr(obj, attr, wrapped)


def _pool(workers: int, pks):
    """Worker processes that import only the benchmark's reference (no
    JAX), started fresh by spawn."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    from . import check

    return ProcessPoolExecutor(
        max_workers=workers,
        mp_context=multiprocessing.get_context("spawn"),
        initializer=check.init_worker,
        initargs=(pks,),
    )


# -- the run --------------------------------------------------------------------------


def main(argv=None, hooks=None) -> int:
    """hooks (tests and the control only): root (where the cell's files
    are), any_device (skip the look for a chip), make_verifier
    (table, metrics) -> verifier."""
    args = parse_args(argv)
    hooks = hooks or {}
    root = Path(hooks.get("root", ROOT))
    spec = load_cell(root, args.workload)
    loop_cls = load_loop(root, spec["traffic"]["loop"])
    wanted = spec["per_layer"] if args.trace else spec["end_to_end"]
    readers = {m["name"]: load_reader(root, m["name"]) for m in wanted}
    # the compile cache lives in the checkout, at a fixed path; the TPU
    # runtime's own logs would go to a fixed path under /tmp
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    t0 = time.monotonic()
    import jax

    devices = jax.devices()
    dev = devices[0]
    t_init = time.monotonic() - t0
    chips = spec["cell"]["chips"]
    if not hooks.get("any_device") and (dev.platform != "tpu" or len(devices) < chips):
        print(
            f"benchmark: needs {chips} TPU chip(s); JAX has "
            f"{len(devices)} {dev.platform!r} device(s)",
            file=sys.stderr,
        )
        return 2
    _log(f"device: {dev.device_kind} x{len(devices)} ({dev.platform})")

    from lodestar_tpu.utils import compile_cache

    _log(f"compile cache: {compile_cache.configure()}")
    # unbounded: a size cap set in the environment evicts this cell's
    # programs while they are written, and every run compiles again
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from lodestar_tpu.kernels import launch

    if dev.platform == "tpu" and launch.interpret():
        raise SystemExit("pallas kernels would run in interpret mode on the chip")

    import numpy as np

    from .reference import bls as RB

    st = {"args": args, "spec": spec, "root": root, "devices": devices, "t_init": t_init}
    st["readers"] = readers
    st["events"] = CompileEvents()
    rngs = [np.random.default_rng(s) for s in np.random.SeedSequence(args.seed).spawn(4)]
    st["rng_check"] = rngs[2]
    st["sks"] = make_keys(rngs[0], spec["config"]["distinct_keypairs"])
    pks = [RB.sk_to_pk(sk) for sk in st["sks"]]
    loop = st["loop"] = loop_cls(spec["config"], spec["traffic"], rngs[1], st["sks"])
    jobs = loop.signing_jobs
    # a few signing workers, so that the warm-up's tracing (one thread,
    # most of set-up) keeps its cores; they finish long before it does
    with _pool(SIGNING_WORKERS, pks) as signer:
        signing = [signer.submit(RB.sign_many, jobs[i : i + 64]) for i in range(0, len(jobs), 64)]
        try:
            _measure(st, hooks, pks, signing, rngs[3])
        except BaseException:
            signer.shutdown(wait=True, cancel_futures=True)
            raise
    # the reference runs once the node is freed, on every spare core
    with _pool(os.cpu_count() or 1, pks) as executor:
        return _finish(st, executor)


def _measure(st, hooks, pks, signing, rng_warm) -> None:
    """Table, node, warm-up and the window; fills `st` with what the
    window did, reads the memory peak, and frees the node."""
    import jax

    from lodestar_tpu.bls.pipeline import create_bls_service
    from lodestar_tpu.bls.pubkey_table import PubkeyTable
    from lodestar_tpu.utils.metrics import BlsPoolMetrics, Registry

    args, cfg, loop = st["args"], st["spec"]["config"], st["loop"]
    t0 = time.monotonic()
    table = PubkeyTable(capacity=cfg["validators"])
    table.register_points_unchecked(pks, tile_to=cfg["validators"])
    for plane in table.device_planes():
        plane.block_until_ready()
    t_table = time.monotonic() - t0
    _log(
        f"world: {cfg['validators']} table rows on device, {loop.describe()}, "
        f"{len(signing)} signing chunks on {SIGNING_WORKERS} workers, {t_table:.3f}s"
    )

    # -- the node, as the node composes it ------------------------------------
    registry = Registry()
    verifier = hooks.get("make_verifier", tpu_verifier)(table, BlsPoolMetrics(registry))
    sup = getattr(verifier, "supervisor", None)
    if sup is not None and not sup.enabled:
        raise SystemExit("the breaker is off; the configuration states it on")
    service = create_bls_service(verifier)
    node = {"service": service, "verifier": verifier, "registry": registry}

    # -- warm-up ----------------------------------------------------------------
    t0 = time.monotonic()
    loop.first_call(service, rng_warm, FIRST_CALL_S)
    t_first = time.monotonic() - t0
    _log(f"first call (compiles or loads every program): {t_first:.3f}s")
    t0 = time.monotonic()
    loop.attach([s for f in signing for s in f.result()])
    t_wait = time.monotonic() - t0
    t0 = time.monotonic()
    loop.settle(service, SETTLE_REQUESTS, FIRST_CALL_S)
    t_settle = time.monotonic() - t0
    shapes = st["shapes"] = dispatched_shapes(verifier)
    if shapes is not None:
        _log(f"warmed shapes (entry, n, k): {sorted(shapes)}")
        if not loop.shapes <= shapes:
            raise SystemExit(f"warm-up missed shapes {sorted(loop.shapes - shapes)}")
    events = st["events"]
    st["setup_s"] = time.monotonic() - T_PROCESS
    split = {
        "total": st["setup_s"],
        "device_init": st["t_init"],
        "table": t_table,
        "first_call": t_first,
        "signing_wait": t_wait,
        "settle": t_settle,
        **events.split(),
    }
    _log("setup_s split (s): " + json.dumps({k: round(v, 4) for k, v in split.items()}))

    # -- the window ---------------------------------------------------------------
    span = None
    if args.trace:
        # only now: a kernel's lowered form records the Python stack that
        # traced it, so a wrapper on that stack during the warm-up would
        # miss every program in the compile cache
        annotate(verifier, "begin_job", "bench.begin_job")
        annotate(verifier, "finish_job", "bench.finish_job")
        annotate(getattr(verifier, "messages", None), "get_many", "bench.hash_roots")
        import tempfile

        st["trace_dir"] = tempfile.mkdtemp(prefix="bench-trace-")
        # annotations and device ops only: no Python tracer, no HLO protos
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(st["trace_dir"], profiler_options=opts)
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()

    def end_trace():
        if span is not None:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    samplers = {n: r.sample for n, r in st["readers"].items() if hasattr(r, "sample")}
    samples = {n: fn(node) for n, fn in samplers.items()}
    st["before"] = counters(verifier, registry)
    events_before = Counter(events.count)
    loop.run(
        service, time.monotonic(), args.seconds, DRAIN_S,
        (min(TRACE_S, args.seconds), end_trace),
        jax.profiler.TraceAnnotation if args.trace else None,
    )
    st["after"] = counters(verifier, registry)
    st["samples"] = {n: (samples[n], fn(node)) for n, fn in samplers.items()}
    st["in_window"] = dict(events.count - events_before)
    st["shapes_after"] = dispatched_shapes(verifier)
    st["peak"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in st["devices"]
    )
    service.close()


def _finish(st, executor) -> int:
    """Everything after the node is freed: what the window did, the
    comparison with the reference, the metrics and the result line."""
    args, loop = st["args"], st["loop"]
    before, after, shapes = st["before"], st["after"], st["shapes"]

    # -- what the window did ---------------------------------------------------------
    delta = {k: after[k] - before.get(k, 0) for k in after}
    new_shapes = sorted(st["shapes_after"] - shapes) if shapes is not None else []
    trace = None
    if args.trace:
        from . import trace_reduce

        trace_dir = st["trace_dir"]
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        if files:
            trace = trace_reduce.reduce(trace_reduce.load(str(files[-1])))
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    traced_s = trace["window_s"] if trace else 0.0
    done = loop.report(traced_s)
    _log(
        f"window: {done['log']}; compiles/traces/lowerings in window "
        f"{json.dumps(st['in_window'])}, pallas builds {delta['pallas_builds']}, new shapes {new_shapes}"
    )
    if "msg_cache_hits" in delta:
        _log(
            f"message cache in window: {delta['msg_cache_hits']} hits, "
            f"{delta['msg_cache_misses']} misses"
        )

    # -- the comparison with the plain reference ------------------------------------
    t0 = time.monotonic()
    result = loop.check(executor, st["rng_check"])
    _log(f"reference: {result['log']}, {time.monotonic() - t0:.3f}s")
    checks = {
        **result["numbers"],
        "host_fallback_sets": {"value": int(after["host_fallback_sets"]), "limit": 0},
    }
    correct = result["compared"] > 0 and all(c["value"] <= c["limit"] for c in checks.values())

    # -- metrics -------------------------------------------------------------------------
    ctx = {
        "setup_s": st["setup_s"],
        "window_s": args.seconds,
        "counters": delta,
        "samples": st["samples"],
        "trace": trace,
        **done["ctx"],
    }
    wanted = st["spec"]["per_layer"] if args.trace else st["spec"]["end_to_end"]
    metrics = {}
    for m in wanted:
        value = st["readers"][m["name"]].read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = st["devices"][0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(st["devices"]),
        "memory_peak_bytes": st["peak"],
    }
    out = {
        "correct": bool(correct),
        "attempted": done["attempted"],
        "failed": done["failed"],
        "metrics": metrics,
        "device": device,
    }
    if args.trace and trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace["device_ops"]],
            "idle_gaps": [[n, s] for n, s in trace["idle_gaps"]],
        }
        _log(f"trace: {trace['op_count']} device ops, {trace['gap_count']} idle gaps")
    out["checks"] = checks
    _log(f"total {time.monotonic() - T_PROCESS:.3f}s")
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
