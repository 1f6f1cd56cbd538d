"""One run of one benchmark cell, driven by data.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix;
this module finds each by its name (``configs/<config>.json``,
``traffic/<traffic>.json``, ``limits/<cell>.json``, and each per-layer
metric's reader ``metrics/<metric>.py``), so a new cell, mix,
configuration or metric is a new file and a new entry, never an edit.

A configuration may own operations of its own (a model run as a query
operation): ``"ops": {"<op>": {<sizes, source, assumed>}}``.  Each is
two files found by the op's name: ``references/<op>.py``, the plain
reference and the op's data drawn from the seed (see
``bench/reference.py``), and ``ops/<op>.py``, whose
``install(engine, spec, weights, devices)`` registers the op with the
engine through the program's public UDF API, as a client would.

A run: generate the collection and each owned op's weights from the
seed, build the engine the configuration describes, install its owned
ops, ingest, warm up every shape the mix uses, drive the closed-loop
window from client threads in this process, read the device's memory
peak, shut the engine down and drop it, compare what the window
produced with the reference, and return the result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import threading
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
RESULT_TIMEOUT_S = 60.0     # how long past the window an answer may come
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


# ------------------------------------------------------------- the cell
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list          # metric entries measured in this cell
    bench_dir: str

    @property
    def ops(self) -> dict:
        """The configuration's own operations: name -> spec."""
        return self.config.get("ops", {})


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, benchmark: str | None = None,
              bench_dir: str = BENCH) -> Cell:
    """The cell ``name`` of ``benchmark`` (default ``<root>/BENCHMARK.json``)
    with its configuration, traffic and limits read from ``bench_dir``."""
    spec = _load(benchmark or os.path.join(os.path.dirname(bench_dir),
                                           "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]

    def applies(metric):
        return name in metric.get("workloads", [name])
    from bench.traffic import validate
    traffic = _load(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    validate(traffic)
    return Cell(
        name=name, chips=w["chips"],
        config=_load(os.path.join(bench_dir, "configs",
                                  w["config"] + ".json")),
        traffic=traffic,
        limits=_load(os.path.join(bench_dir, "limits", name + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)],
        bench_dir=bench_dir)


def load_module(bench_dir: str, sub: str, name: str):
    """The module ``<bench_dir>/<sub>/<name>.py``, loaded from its file."""
    path = os.path.join(bench_dir, sub, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{sub}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: str, metric: str):
    """``read(readings)`` of ``metrics/<metric>.py``."""
    return load_module(bench_dir, "metrics", metric).read


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at one fixed directory inside
    the checkout, ``<checkout>/.jax_cache``, given to the program too
    (``JAX_COMPILATION_CACHE_DIR``), with every compile persisted, the
    small eager-op programs too, so that only a checkout's first run
    compiles and two checkouts share nothing."""
    import jax
    from repro.launch.compile_cache import ENV, use_compile_cache
    path = os.path.join(os.path.dirname(BENCH), ".jax_cache")
    os.environ[ENV] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return use_compile_cache()


# ------------------------------------------------------------- engine
def build_engine(config: dict):
    from repro.core.engine import VDMSAsyncEngine
    from repro.core.remote import TransportModel
    kw = dict(config["engine"])
    if "transport" in kw:
        kw["transport"] = TransportModel(**kw["transport"])
    return VDMSAsyncEngine(**kw)


def own_op_data(cell: Cell, seed: int) -> dict:
    """Each of the configuration's own ops: name -> {"spec", "weights"},
    the weights drawn from the seed by ``references/<op>.py``'s
    ``weights(spec, seed)`` (none where it has no such function)."""
    from bench import reference
    data = {}
    for name, spec in cell.ops.items():
        ref = reference.own_op(name, cell.bench_dir)
        draw = getattr(ref, "weights", None)
        data[name] = {"spec": spec,
                      "weights": draw(spec, seed) if draw else {}}
    return data


def install_ops(eng, cell: Cell, op_data: dict, devices) -> None:
    """``ops/<op>.py``'s ``install`` for each of the configuration's own
    ops, given the same weights the reference will use."""
    for name, d in op_data.items():
        load_module(cell.bench_dir, "ops", name).install(
            eng, d["spec"], d["weights"], devices)


def _flatten(prefix: str, obj, out: dict):
    if isinstance(obj, dict):
        for k, v in obj.items():
            _flatten(f"{prefix}.{k}", v, out)
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix] = obj


def snapshot(eng) -> dict:
    """The engine's counters at one instant, flat: ``util.*``,
    ``dispatch.*``, ``admission.*`` from its stats calls, the event
    loop's lifetime busy seconds (``loop.*``) and the device workers'
    row and byte counters (``device.*``)."""
    out = {"time": time.monotonic()}
    _flatten("util", eng.utilization(), out)
    _flatten("dispatch", eng.dispatch_stats(), out)
    _flatten("admission", eng.admission_stats(), out)
    meters = eng.loop.t2_meter.meters
    out["loop.t3_busy_s"] = eng.loop.t3_meter.total_busy_s
    out["loop.native_busy_s"] = sum(m.total_busy_s for m in meters)
    out["loop.native_workers"] = len(meters)
    dev = eng.device_backend
    if dev is not None:
        workers = getattr(dev, "workers", [dev])
        for attr in ("stacked_rows", "pad_rows", "h2d_bytes", "d2h_bytes",
                     "entities_run", "groups_run", "compiles"):
            out[f"device.{attr}"] = sum(getattr(w, attr) for w in workers)
    return out


# ------------------------------------------------------- compile counts
class CompileCounter:
    """Counts XLA compiles (``backend_compile`` events, which a load from
    the persistent cache also raises) and persistent-cache hits, through
    ``jax.monitoring``.  JAX keeps listeners for the life of the process,
    so one counter serves every run in it."""

    _installed = None

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._installed is None:
            import jax
            counter = cls()

            def on_duration(event, duration, **_):
                if event == BACKEND_COMPILE:
                    counter.compiles += 1

            def on_event(event, **_):
                if event == CACHE_HIT:
                    counter.cache_hits += 1
            jax.monitoring.register_event_duration_secs_listener(on_duration)
            jax.monitoring.register_event_listener(on_event)
            cls._installed = counter
        return cls._installed

    def count(self) -> tuple[int, int]:
        return self.compiles, self.cache_hits


# ------------------------------------------------------------- warm-up
def warm_up(eng, cell: Cell, seed: int, log, max_passes: int = 6,
            burst_s: float = 2.0) -> None:
    """Every query of the mix over each warm-up size (the batch buckets
    the configuration lists), pass after pass until a pass compiles
    nothing, or as many as the pass before it: what is left then is
    compiled anew on every call, which no warm-up clears.  Eager ops run
    on whichever worker thread picks an entity up, so one pass need not
    reach every program the window will ask for.

    Each device worker compiles its own programs, and where the
    configuration has several (``num_device_workers``) the router
    decides which worker forms a group of which size.  So then the mix
    itself is driven, by as many clients as the window has and on query
    streams of its own, in bursts of ``burst_s`` until a burst compiles
    nothing."""
    from bench.traffic import by_index
    sizes = cell.config["warmup_sizes"]
    counter = CompileCounter.get()
    last = None
    for rnd in range(max_passes):
        before = counter.count()
        for name in sorted(cell.traffic["queries"]):
            ops = cell.traffic["queries"][name]
            lo = 0
            for size in sizes:
                res = eng.submit(by_index(lo, lo + size - 1, ops)).result(600)
                lo += size
                if res["stats"]["failed"]:
                    raise RuntimeError(f"warm-up query {name} over {size} "
                                       f"faces failed")
        after = counter.count()
        log(f"warm-up pass {rnd + 1}: {after[0] - before[0]} compiles, "
            f"{after[1] - before[1]} persistent-cache hits")
        compiled = after[0] - before[0]
        if compiled in (0, last):
            break
        last = compiled
    if cell.config["engine"].get("num_device_workers", 1) == 1:
        return
    for rnd in range(max_passes):
        before = counter.count()
        recs = run_clients(eng, cell, seed, burst_s,
                           first_client=(rnd + 1) * cell.traffic["clients"])
        after = counter.count()
        log(f"warm-up burst {rnd + 1}: {len(recs)} queries, "
            f"{after[0] - before[0]} compiles, "
            f"{after[1] - before[1]} persistent-cache hits")
        bad = [r for r in recs if r.error or r.failed_entities]
        if bad:
            raise RuntimeError(f"warm-up burst: {len(bad)} queries failed, "
                               f"first: {bad[0].error}")
        if after[0] == before[0]:
            return


# ------------------------------------------------------------- window
@dataclasses.dataclass
class Record:
    query: object
    t_submit: float
    t_done: float | None = None
    error: str | None = None
    eids: list | None = None
    failed_entities: int = 0
    outputs: dict | None = None
    deliveries: list = dataclasses.field(default_factory=list)

    def on_entity(self, ent):
        with _span("bench.on_entity"):
            self.deliveries.append((time.monotonic(), ent.eid,
                                    bool(ent.failed)))


@contextlib.contextmanager
def _span(name: str):
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def client_loop(eng, queries, end: float, deadline: float, records: list):
    """Closed loop: submit, wait for the answer, repeat until ``end``."""
    while time.monotonic() < end:
        q = next(queries)
        rec = Record(query=q, t_submit=time.monotonic())
        records.append(rec)
        try:
            with _span("bench.submit"):
                fut = eng.submit(q.json(), on_entity=rec.on_entity)
            with _span("bench.wait"):
                res = fut.result(max(deadline - time.monotonic(), 1e-3))
            rec.t_done = time.monotonic()
            rec.eids = list(res["entities"])
            rec.failed_entities = res["stats"]["failed"]
            if q.checked:
                rec.outputs = res["entities"]
        except Exception as e:  # noqa: BLE001 — a failed query is counted
            rec.t_done = time.monotonic()
            rec.error = f"{type(e).__name__}: {e}"


def start_clients(eng, cell: Cell, seed: int, end: float, deadline: float,
                  first_client: int = 0):
    """The mix's closed-loop clients, started, sending until ``end``:
    client ``i`` sends query stream ``first_client + i`` of the seed.
    Returns the threads and each client's list of records."""
    from bench.traffic import stream
    n = cell.traffic["clients"]
    records = [[] for _ in range(n)]
    threads = [threading.Thread(
        target=client_loop, name=f"bench-client-{i}",
        args=(eng, stream(cell.traffic, cell.config["collection"], seed,
                          first_client + i),
              end, deadline, records[i])) for i in range(n)]
    for t in threads:
        t.start()
    return threads, records


def run_clients(eng, cell: Cell, seed: int, seconds: float,
                first_client: int) -> list:
    """The mix's clients for ``seconds`` outside the window; returns
    their records once every answer is in."""
    end = time.monotonic() + seconds
    deadline = end + RESULT_TIMEOUT_S
    threads, records = start_clients(eng, cell, seed, end, deadline,
                                     first_client)
    for t in threads:
        t.join(max(deadline - time.monotonic(), 0.0) + 5.0)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client outside the window never returned")
    return [r for rs in records for r in rs]


def drive(eng, cell: Cell, seed: int, seconds: float, trace_dir: str | None,
          trace_seconds: float, log) -> dict:
    """The measured window; returns the records, the window's edges and
    the counter snapshots."""
    counter = CompileCounter.get()
    snaps = {}
    compiles0 = counter.count()
    snaps["start"] = snapshot(eng)
    t0 = time.monotonic()
    end = t0 + seconds
    deadline = end + RESULT_TIMEOUT_S
    threads, records = start_clients(eng, cell, seed, end, deadline)
    if trace_dir is not None:
        import jax
        lead = max(0.0, (seconds - trace_seconds) / 2)
        time.sleep(max(0.0, t0 + lead - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        snaps["trace_start"] = snapshot(eng)
        with _span("bench.traced_window"):
            time.sleep(trace_seconds)
        snaps["trace_end"] = snapshot(eng)
        jax.profiler.stop_trace()
    time.sleep(max(0.0, end - time.monotonic()))
    snaps["end"] = snapshot(eng)
    compiles1 = counter.count()
    for t in threads:
        t.join(max(deadline - time.monotonic(), 0.0) + 5.0)
    log(f"compiles in the window: {compiles1[0] - compiles0[0]} "
        f"(persistent-cache hits {compiles1[1] - compiles0[1]})")
    stuck = sum(t.is_alive() for t in threads)
    return {"records": [r for rs in records for r in rs], "end": end,
            "snaps": snaps, "stuck_clients": stuck}


# ------------------------------------------------------------ readings
class Readings:
    """What a per-layer metric's reader may read: counter deltas over the
    window (``span="window"``) or the traced part of it
    (``span="trace"``), the reduced trace, the peaks of the chip, and the
    cell's configuration and mix."""

    def __init__(self, cell: Cell, snaps: dict, trace: dict | None,
                 peaks: dict | None):
        self.cell = cell
        self.snaps = snaps
        self.trace = trace
        self.peaks = peaks

    def delta(self, key: str, span: str = "window"):
        a, b = (("start", "end") if span == "window"
                else ("trace_start", "trace_end"))
        if a not in self.snaps or key not in self.snaps[a]:
            return None
        return self.snaps[b][key] - self.snaps[a][key]

    @property
    def image_shape(self) -> tuple:
        c = self.cell.config["collection"]
        return (c["size"], c["size"], c["channels"])


def percentile(values, q: float):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if values else None


def end_to_end(window: dict, seconds: float, setup_s: float) -> dict:
    end, recs = window["end"], window["records"]
    done = [r for r in recs if r.t_done is not None and r.t_done <= end
            and r.error is None]
    delivered = sum(1 for r in recs for t, _, failed in r.deliveries
                    if t <= end and not failed)
    firsts = [min(t for t, _, _ in r.deliveries) - r.t_submit
              for r in done if r.deliveries]
    return {
        "entities_per_s": delivered / seconds,
        "query_p95_ms": percentile([(r.t_done - r.t_submit) * 1e3
                                    for r in done], 95),
        "first_entity_p95_ms": percentile([f * 1e3 for f in firsts], 95),
        "setup_s": setup_s,
        "queries_done": len(done),
    }


def query_checks(recs: list, eid_of: dict, props: list) -> dict:
    """The exact numbers: failed queries, wrong selections, wrong
    deliveries, over every query the window sent."""
    from bench.faces import select
    failed = wrong_sel = wrong_del = 0
    for r in recs:
        if r.error is not None or r.failed_entities:
            failed += 1
            continue
        q = r.query
        want = sorted(eid_of[i] for i in select(props, q.category, q.age_lo,
                                                q.age_hi))
        if sorted(r.eids) != want:
            wrong_sel += 1
        got = sorted(e for _, e, _ in r.deliveries)
        if got != sorted(r.eids):
            wrong_del += 1
    return {"failed_queries": failed, "wrong_selections": wrong_sel,
            "wrong_deliveries": wrong_del}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


# ------------------------------------------------------------------ run
def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_process: float, devices, log, trace_seconds: float = 3.0,
        keep_trace: str | None = None, control: bool = False) -> dict:
    """One run of ``cell``; returns the result line as a dict (plus
    ``_control``, the control's numbers, when ``control``)."""
    import jax
    from bench import check, counts, faces
    from bench.trace_reduce import reduce

    coll = cell.config["collection"]
    log(f"cell {cell.name}: seed {seed}, {seconds} s window, trace {trace}")
    phases = [("imports+devices", time.monotonic())]
    images = faces.generate(seed, coll["faces"], coll["size"])
    props = faces.properties(seed, coll["faces"], coll["categories"],
                             coll["age_min"], coll["age_max"])
    phases.append(("faces", time.monotonic()))
    op_data = own_op_data(cell, seed)
    if op_data:
        phases.append(("weights", time.monotonic()))
    eng = build_engine(cell.config)
    try:
        install_ops(eng, cell, op_data, devices)
        eid_of = {i: eng.add_entity("image", images[i], props[i])
                  for i in range(len(images))}
        phases.append(("engine+ingest", time.monotonic()))
        warm_up(eng, cell, seed, log)
        t_setup = time.monotonic()
        phases.append(("warm-up", t_setup))
        setup_s = t_setup - t_process
        log("set-up: " + ", ".join(
            f"{name} {t - t_prev:.3f} s" for (_, t_prev), (name, t) in
            zip([("process", t_process)] + phases, phases)))
        with contextlib.ExitStack() as stack:
            tdir = None
            if trace:
                tdir = keep_trace or stack.enter_context(
                    tempfile.TemporaryDirectory(prefix="bench-trace-"))
            window = drive(eng, cell, seed, seconds, tdir,
                           min(trace_seconds, seconds), log)
            # only a TPU's trace has device planes to reduce
            reduced = (reduce(tdir) if trace and devices[0].platform == "tpu"
                       else None)
        mem = memory_peak(devices)
        snaps = window["snaps"]
    finally:
        eng.shutdown()
    # the reference may need the device memory the engine held
    del eng
    gc.collect()
    recs = window["records"]
    numbers = query_checks(recs, eid_of, props)
    numbers["failed_queries"] += window["stuck_clients"]
    idx_of = {e: i for i, e in eid_of.items()}
    checked = [(r.query.operations,
                [(idx_of[e], a) for e, a in r.outputs.items()])
               for r in recs if r.outputs is not None]
    ref_kw = dict(bench_dir=cell.bench_dir, op_data=op_data, chip=devices[0])
    numbers.update(check.output_numbers(checked, images, **ref_kw))
    correct, table = check.decide(numbers, cell.limits)
    e2e = end_to_end(window, seconds, setup_s)
    log(f"queries done in the window: {e2e['queries_done']}; "
        f"sampled for pixels: {len(checked)}")
    dev0 = devices[0]
    result = {
        "correct": correct,
        "attempted": len(recs),
        "failed": numbers["failed_queries"],
        "metrics": {},
        "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": mem},
    }
    if not trace:
        for m in cell.end_to_end:
            value = e2e.get(m["name"])
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        peaks = (counts.peaks(dev0.device_kind)
                 if dev0.platform == "tpu" else None)
        readings = Readings(cell, snaps, reduced, peaks)
        for m in cell.per_layer:
            value = load_reader(cell.bench_dir, m["name"])(readings)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if reduced is not None:
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    if control:
        result["_control"] = check.output_numbers(
            checked, images, control_device=devices[0], **ref_kw)
    result["checks"] = table
    return result


def finite(x):
    """JSON has no inf or nan: such a number is written as a string."""
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    return x


def print_result(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The compared numbers with their limits as the last lines of
    stderr, then the result object as the last line of stdout."""
    for name, row in result["checks"].items():
        print(f"check {name}: {row['value']!r} (limit {row['limit']!r})",
              file=err, flush=True)
    print(json.dumps(finite({k: v for k, v in result.items()
                             if not k.startswith("_")})),
          file=out, flush=True)
