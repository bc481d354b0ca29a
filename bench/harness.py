"""One run of one benchmark cell: set-up, measured window, check.

The harness is driven by data.  A cell named in ``BENCHMARK.json`` finds

* its mix in ``bench/workloads/<cell>.json`` (read by :mod:`bench.traffic`),
* its configuration in ``bench/configs/<config>.json``,
* each metric's reader in ``bench/metrics/<metric>.py`` (``read(run)``),
* its family's FLOP counts in ``bench/flops/<family>.py`` and its plain
  reference in ``bench/reference/<family>.py``,

so a later cell, configuration or metric is added as files and entries.

From the program the harness takes the system under test
(``InstanceManager`` -> ``ServingEngine`` -> ``AsyncPlatform``) and wraps
its calls into the engine's compiled steps, the KV gather, the wake and
the deflate with its own timers and ``jax.profiler.TraceAnnotation``
spans (``bench.*``).  It edits nothing of the program.
"""
from __future__ import annotations

import gc
import hashlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import traffic as traffic_mod

ROOT = Path(__file__).resolve().parents[1]
#: a request still unanswered this long after the window closed never came
DRAIN_S = 60.0
#: memory sampling period in the window
SAMPLE_S = 0.5


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CheckFailed(RuntimeError):
    """The benchmark's own set-up or data is inconsistent."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ====================================================================== spec
@dataclass
class Spec:
    root: Path
    cell: dict                  # the BENCHMARK.json workloads entry
    mix: dict                   # bench/workloads/<cell>.json
    conf: dict                  # bench/configs/<config>.json
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def name(self) -> str:
        return self.cell["name"]


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_spec(cell_name: str, root: Path = ROOT) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if cell_name not in cells:
        raise CheckFailed(f"no cell {cell_name!r} in BENCHMARK.json")
    cell = cells[cell_name]
    mix = json.loads((root / "bench" / "workloads"
                      / f"{cell_name}.json").read_text())
    if (mix["config"], mix["traffic"]) != (cell["config"], cell["traffic"]):
        raise CheckFailed(f"{cell_name}: BENCHMARK.json names "
                          f"{cell['config']}/{cell['traffic']}, the mix file "
                          f"{mix['config']}/{mix['traffic']}")
    conf = json.loads((root / "bench" / "configs"
                       / f"{mix['config']}.json").read_text())
    return Spec(root, cell, mix, conf,
                [m for m in bench["end_to_end"] if applies(m, cell_name)],
                [m for m in bench["per_layer"] if applies(m, cell_name)])


def load_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family(kind: str, conf: dict):
    """``bench.<kind>.<family>`` for the configuration's family."""
    return importlib.import_module(f"bench.{kind}.{conf['family']}")


def tenant_key(seed: int, tenant: int) -> np.ndarray:
    """The raw uint32[2] PRNG key of one tenant's weights."""
    return np.random.SeedSequence([seed % (1 << 64), 7, tenant]
                                  ).generate_state(2, np.uint32)


def weight_bytes(conf: dict) -> int:
    """Bytes of one tenant's weights, from the reference's shapes."""
    import jax
    ref = family("reference", conf)
    shapes = jax.eval_shape(lambda k: ref.init(k, conf),
                            np.zeros(2, np.uint32))
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))


def bucket(n: int) -> int:
    """The decode cache length the engine allocates for ``n`` tokens."""
    b = 16
    while b < n:
        b *= 2
    return b


# ====================================================================== probes
@dataclass
class Rec:
    """One request: what was planned and what the client saw."""

    plan: traffic_mod.Planned
    due: float = 0.0            # scheduled (open loop) or sent (closed loop)
    sent: float = 0.0
    token_times: List[float] = field(default_factory=list)
    done: Optional[float] = None
    resp: object = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return (self.resp is not None and self.error is None
                and len(self.resp.tokens) == self.plan.max_new)


class Probes:
    """What the benchmark's wrappers saw, each with monotonic times."""

    def __init__(self):
        self.lock = threading.Lock()
        self.dispatches: List[tuple] = []   # (kind, B, Sb, host_bytes, t0, t1)
        self.kv_gathers: List[tuple] = []   # (t0, t1)
        self.wakes: List[tuple] = []        # (tenant, state, trigger, t0, t1, performed)
        self.compiles: List[tuple] = []     # (event, t, secs)

    def add(self, what: str, row: tuple) -> None:
        with self.lock:
            getattr(self, what).append(row)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def make_engine_class():
    """The program's engine with the benchmark's timers around its calls."""
    import jax
    from repro.serving import ServingEngine

    class BenchEngine(ServingEngine):
        probes: Probes

        def _compiled(self, inst, kind, B, Sb, *rest):
            fn = super()._compiled(inst, kind, B, Sb, *rest)
            probes = self.probes

            def timed(params, *args):
                host = sum(leaf.nbytes for leaf in jax.tree.leaves(params)
                           if isinstance(leaf, np.ndarray))
                with span(f"bench.dispatch.{kind}"):
                    t = time.monotonic()
                    out = jax.block_until_ready(fn(params, *args))
                    probes.add("dispatches",
                               (kind, B, Sb, host, t, time.monotonic()))
                return out
            return timed

        def _dense_cache(self, inst, sids, max_len):
            with span("bench.kv_gather"):
                t = time.monotonic()
                out = jax.block_until_ready(
                    super()._dense_cache(inst, sids, max_len))
                self.probes.add("kv_gathers", (t, time.monotonic()))
            return out

    return BenchEngine


def wrap_manager(mgr, probes: Probes) -> None:
    """Time the manager's wake and span its deflate."""
    ensure_awake, descend = mgr.ensure_awake, mgr.descend

    def timed_wake(iid, trigger="request", priority=None):
        inst = mgr.instances.get(iid)
        state = inst.state.value if inst is not None else None
        with span("bench.wake"):
            t = time.monotonic()
            st = ensure_awake(iid, trigger=trigger, priority=priority)
            probes.add("wakes", (iid, state, trigger, t, time.monotonic(),
                                 st is not None))
        return st

    def traced_descend(iid, rung, **kw):
        with span("bench.deflate"):
            return descend(iid, rung, **kw)

    mgr.ensure_awake, mgr.descend = timed_wake, traced_descend


def count_compiles(probes: Probes):
    """Record lowerings and XLA compiles; returns the listener to remove."""
    from jax import monitoring

    def listen(name, secs, **_):
        if name in ("/jax/core/compile/backend_compile_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            probes.add("compiles", (name.rsplit("/", 1)[-1],
                                    time.monotonic(), secs))
    monitoring.register_event_duration_secs_listener(listen)
    return listen


def use_compile_cache(root: Path = ROOT) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program in it."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class MemorySampler(threading.Thread):
    """Host VmRSS plus the device's bytes in use, every ``SAMPLE_S``."""

    def __init__(self, device):
        super().__init__(name="bench-memory", daemon=True)
        self.device = device
        self.samples: List[tuple] = []      # (t, rss_bytes, device_bytes)
        self.stop_ev = threading.Event()

    @staticmethod
    def rss() -> int:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
        raise CheckFailed("no VmRSS in /proc/self/status")

    def sample(self) -> None:
        stats = self.device.memory_stats() or {}
        self.samples.append((time.monotonic(), self.rss(),
                             int(stats.get("bytes_in_use", 0))))

    def run(self) -> None:
        while True:
            self.sample()
            if self.stop_ev.wait(SAMPLE_S):
                return

    def stop(self) -> None:
        self.stop_ev.set()
        self.join()


def bytes_written() -> int:
    """Bytes this process has passed to write calls (``wchar``)."""
    with open("/proc/self/io") as f:
        for line in f:
            if line.startswith("wchar:"):
                return int(line.split()[1])
    return 0


# ====================================================================== run
@dataclass
class Run:
    """Everything a metric reader may read about one run."""

    spec: Spec
    seed: int
    seconds: float
    t_start: float              # process start (monotonic)
    t0: float = 0.0             # window opens
    t1: float = 0.0             # window closes
    records: List[Rec] = field(default_factory=list)
    probes: Probes = field(default_factory=Probes)
    memory: List[tuple] = field(default_factory=list)
    memory_peak_bytes: int = 0
    trace: Optional[dict] = None     # bench.tracing.reduce output
    device: object = None
    peaks: dict = field(default_factory=dict)
    flops: object = None
    weight_bytes: int = 0
    #: tenant -> {weight leaf path: blake2b} once every tenant is resident
    check_digests: Dict[str, Dict[str, str]] = field(default_factory=dict)
    #: closed-loop client threads (joined by ``drain``)
    clients: List[threading.Thread] = field(default_factory=list)

    @property
    def conf(self) -> dict:
        return self.spec.conf

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    def due(self) -> List[Rec]:
        """Requests due inside the window."""
        return [r for r in self.records if self.t0 <= r.due <= self.t1]


def require_chip(spec: Spec):
    """The cell's chips, or :class:`NoChip`."""
    import jax
    devs = jax.devices()
    want = int(spec.cell.get("chips", 1))
    if devs[0].platform != "tpu" or len(devs) < want:
        raise NoChip(f"cell {spec.name} needs {want} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[0]


def peaks_for(kind: str, root: Path) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())["kinds"]
    if kind not in table:
        raise CheckFailed(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def program_config(conf: dict):
    """The program's ModelConfig holding the configuration file's sizes."""
    import dataclasses
    from repro.configs import SSMConfig, get_config

    fields = ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "attention", "rope_mode",
              "rope_theta", "activation", "norm", "tie_embeddings",
              "hybrid_parallel_ssm", "dtype")
    upd = {k: conf[k] for k in fields}
    if conf.get("ssm"):
        upd["ssm"] = SSMConfig(**conf["ssm"])
    if conf.get("sliding_window"):
        upd["sliding_window"] = conf["sliding_window"]
    cfg = dataclasses.replace(get_config(conf["program_arch"]), **upd)
    for k, v in upd.items():
        if getattr(cfg, k) != v:
            raise CheckFailed(f"program config {k}={getattr(cfg, k)} != {v}")
    return cfg


class System:
    """The system under test for one run, built as ``repro.launch.serve``
    builds it, with the benchmark's probes around its calls."""

    def __init__(self, run: Run, spool: Path):
        import jax
        from repro.core.manager import InstanceManager, ManagerConfig
        from repro.models import model
        from repro.serving import AsyncPlatform, PlatformPolicy

        spec = run.spec
        self.run, self.spool = run, spool
        self.cfg = program_config(spec.conf)
        self.tenants = [f"t{i}" for i in range(int(spec.mix["tenants"]))]
        init = jax.jit(model.init_params, static_argnums=1)
        cfg, seed = self.cfg, run.seed

        def factory(arch_key):
            # one arch key per tenant: tenants hold different weights, and
            # the prefix registry shares pages only within one key
            t = int(arch_key.rsplit("#", 1)[1])
            return cfg, init(tenant_key(seed, t), cfg)

        shutil.rmtree(spool, ignore_errors=True)
        spool.mkdir(parents=True)
        self.mgr = InstanceManager(ManagerConfig(spool_dir=str(spool)),
                                   factory)
        wrap_manager(self.mgr, run.probes)
        Engine = make_engine_class()
        Engine.probes = run.probes
        self.engine = Engine(self.mgr, window=spec.conf.get("sliding_window"))
        pol = spec.mix["policy"]
        self.plat = AsyncPlatform(
            self.engine, PlatformPolicy(keep_warm_s=float(pol["keep_warm_s"])),
            {t: f"{spec.conf['name']}#{i}" for i, t in enumerate(self.tenants)},
            workers=int(pol["workers"]))

    def close(self) -> None:
        if self.mgr.store is not None:
            self.mgr.store.close()
        shutil.rmtree(self.spool, ignore_errors=True)


def warm_up(system: System, tfc: traffic_mod.Traffic, batches: List[int]):
    """Compile every shape this cell's traffic can produce, per tenant
    (the engine keeps compiled steps per instance), with the weights
    placed on the device once so that no warm-up call uploads them."""
    import jax
    import jax.numpy as jnp
    from repro.models import model

    eng, cfg = system.engine, system.cfg
    lens = sorted({bucket(p + o) for p in tfc.prompt_lens()
                   for o in tfc.output_lens()})
    for t in system.tenants:
        inst = system.mgr.instances[t]
        params = jax.device_put(inst.params_pytree())
        for S in tfc.prompt_lens():
            fn = eng._compiled(inst, "prefill", 1, S, False, False)
            fn(params, jnp.zeros((1, S), jnp.int32), None, None)
        for B in batches:
            for L in lens:
                fn = eng._compiled(inst, "decode", B, L, False, False)
                fn(params, jnp.zeros((B,), jnp.int32),
                   model.init_cache(cfg, B, L))
        del params


def set_up(run: Run, system: System, tfc: traffic_mod.Traffic) -> Dict:
    """Cold-start every tenant, warm every shape, serve one priming request
    per tenant (recording its REAP working set), and descend the tenants
    to the mix's starting rung.  Returns seconds per phase."""
    from repro.core.state import Rung
    from repro.serving import Request

    mix, eng, mgr = run.spec.mix, system.engine, system.mgr
    phases = {}
    t = time.monotonic()
    for i, tid in enumerate(system.tenants):
        eng.start_instance(tid, f"{run.spec.conf['name']}#{i}")
    phases["cold_start"] = time.monotonic() - t

    t = time.monotonic()
    warm_up(system, tfc, [int(b) for b in mix["warm_decode_batches"]])
    phases["warm_up"] = time.monotonic() - t

    t = time.monotonic()
    rng = traffic_mod.seed_rng(run.seed, 3)
    serial = iter(range(1 << 30))

    def requests(tid, n, S, O):
        return [Request(tid, f"prime{next(serial)}", rng.integers(
            0, run.conf["vocab_size"], S).astype(np.int32),
            max_new_tokens=O, close_session=True) for i in range(n)]

    S, O = tfc.prompt_lens()[0], tfc.output_lens()[0]
    for tid in system.tenants:
        eng.record_sample(tid, requests(tid, 1, S, O)[0])
    # the host-side steps around the compiled ones (argmax, cache dtype
    # conversion) compile once per process for each batch size and cache
    # length: serve one batch of each shape on the first tenant
    shapes = {}
    for p in tfc.prompt_lens():
        for o in tfc.output_lens():
            shapes.setdefault(bucket(p + o), (p, o))
    for B in [int(b) for b in mix["warm_decode_batches"]]:
        for p, o in shapes.values():
            eng.serve_batch(system.tenants[0],
                            requests(system.tenants[0], B, p, o))
    phases["prime"] = time.monotonic() - t

    rung = mix["setup"].get("descend_to")
    if rung:
        t = time.monotonic()
        for tid in system.tenants:
            with eng.instance_lock(tid):
                mgr.descend(tid, Rung[rung.upper()])
        phases["descend"] = time.monotonic() - t
    return phases


def submit(system: System, rec: Rec) -> None:
    from repro.serving import Request

    plan = rec.plan
    req = Request(system.tenants[plan.tenant], f"s{id(rec)}", plan.prompt,
                  max_new_tokens=plan.max_new, close_session=True,
                  on_token=lambda tok: rec.token_times.append(time.monotonic()))
    rec.sent = time.monotonic()
    with span("bench.submit"):
        fut = system.plat.submit(req)

    def finished(f):
        rec.done = time.monotonic()
        try:
            rec.resp = f.result()
        except Exception as e:       # refused or failed: counted, not raised
            rec.error = f"{type(e).__name__}: {e}"
    fut.add_done_callback(finished)
    return fut


def window(run: Run, system: System, tfc: traffic_mod.Traffic) -> List:
    """Offer the mix's load for ``run.seconds``; returns every future."""
    futs = []
    if tfc.kind == "open_poisson":
        plans = tfc.open_loop(run.seconds)
        run.t0 = time.monotonic()
        run.t1 = run.t0 + run.seconds
        for plan in plans:
            rec = Rec(plan, due=run.t0 + plan.due_s)
            run.records.append(rec)
            delay = rec.due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            futs.append(submit(system, rec))
        time.sleep(max(0.0, run.t1 - time.monotonic()))
        return futs

    streams = [tfc.client_stream(c) for c in range(len(tfc.clients()))]
    lock = threading.Lock()
    run.t0 = time.monotonic()
    run.t1 = run.t0 + run.seconds

    def client(stream):
        # sends until the window closes; its last request may end later
        while time.monotonic() < run.t1:
            rec = Rec(next(stream), due=time.monotonic())
            with lock:
                run.records.append(rec)
            fut = submit(system, rec)
            with lock:
                futs.append(fut)
            try:
                fut.result(timeout=run.t1 + DRAIN_S - time.monotonic())
            except Exception:
                return          # the record says what went wrong

    threads = [threading.Thread(target=client, args=(s,), daemon=True,
                                name=f"bench-client-{i}")
               for i, s in enumerate(streams)]
    for t in threads:
        t.start()
    time.sleep(max(0.0, run.t1 - time.monotonic()))
    run.clients = threads
    return futs


def drain(run: Run, futs: List) -> None:
    """Wait for every request due in the window, up to ``DRAIN_S`` past
    the close; the ones still out by then never came."""
    deadline = run.t1 + DRAIN_S
    for t in run.clients:
        t.join(max(0.0, deadline - time.monotonic()))
    for f in list(futs):
        try:
            f.result(timeout=max(0.0, deadline - time.monotonic()))
        except Exception:
            pass


def digests(weights: Dict[str, np.ndarray]) -> Dict[str, str]:
    def one(a):
        return hashlib.blake2b(np.ascontiguousarray(a).view(np.uint8)
                               ).hexdigest()
    with ThreadPoolExecutor() as ex:
        return dict(zip(weights, ex.map(one, weights.values())))


def resident_digests(system: System) -> Dict[str, Dict[str, str]]:
    """Wake every deflated tenant to full residency and digest its weights
    (the byte check of what the wakes restored)."""
    from repro.core.manager import WAKEABLE_STATES

    out = {}
    for tid in system.tenants:
        inst = system.mgr.instances[tid]
        if inst.state in WAKEABLE_STATES:
            system.mgr.ensure_awake(tid, trigger="sigcont")
        pipe = inst.wake_pipeline
        if pipe is not None and not pipe.wait(timeout=120):
            raise CheckFailed(f"{tid}: wake pipeline did not finish")
        if inst.nonresident_keys():
            raise CheckFailed(f"{tid}: {len(inst.nonresident_keys())} units "
                              f"still swapped out after the wake")
        out[tid] = digests(inst.weights)
    return out


# ====================================================================== main
def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: Path = ROOT):
    """Run one cell once; returns the result line's object and the
    :class:`Run` it was read from."""
    from jax import monitoring

    spec = load_spec(cell_name, root)
    device = require_chip(spec)
    run = Run(spec, seed, seconds, t_start, device=device,
              peaks=peaks_for(device.device_kind, root),
              flops=family("flops", spec.conf))
    run.weight_bytes = weight_bytes(spec.conf)
    listener = count_compiles(run.probes)
    tfc = traffic_mod.Traffic(spec.mix, spec.conf["vocab_size"], seed)
    system = System(run, root / ".bench_spool")
    try:
        phases = set_up(run, system, tfc)
        result = measure(run, system, tfc, trace, phases)
    finally:
        system.close()
        monitoring.unregister_event_duration_listener(listener)
    del system
    gc.collect()
    from bench import oracle
    checks = oracle.check(run)
    result["correct"] = all(oracle.passes(c) for c in checks.values())
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}={c['value']} limit={c['limit']}")
    return result, run


def measure(run: Run, system: System, tfc, trace: bool, phases) -> dict:
    """The window, then the metrics; leaves what the check needs on run."""
    import jax
    from bench import tracing

    log("set-up phases (s): " + " ".join(f"{k}={v:.2f}"
                                          for k, v in phases.items()))
    sampler = MemorySampler(run.device)
    written0 = bytes_written()
    profile_dir = run.spec.root / ".bench_trace"
    system.plat.start()
    if trace:
        shutil.rmtree(profile_dir, ignore_errors=True)
        tracing.start(profile_dir)
    sampler.start()
    with span("bench.window"):
        futs = window(run, system, tfc)
    sampler.stop()
    if trace:
        tracing.stop()
    drain(run, futs)
    system.plat.stop(drain=False, timeout=DRAIN_S)
    run.memory = sampler.samples
    stats = run.device.memory_stats() or {}
    run.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    if trace:
        run.trace = tracing.reduce(*tracing.load(profile_dir), n_devices=1)
        shutil.rmtree(profile_dir, ignore_errors=True)

    due = run.due()
    compiles = [c for c in run.probes.compiles if run.in_window(c[1])]
    late = [r.sent - r.due for r in due]
    log(f"window: {len(due)} requests due, "
        f"{sum(r.ok for r in due)} answered, "
        f"{sum(len(r.token_times) for r in due)} tokens; compiles in window "
        f"{len(compiles)}; generator late by max "
        f"{max(late, default=0.0) * 1e3:.1f} ms; bytes written "
        f"{bytes_written() - written0}; states {system.mgr.states()}")
    hosts = sorted({h for _, _, _, h, t, _ in run.probes.dispatches
                    if run.in_window(t)})
    log(f"host weight bytes per dispatch {hosts}; the configuration's "
        f"weights are {run.weight_bytes} bytes")
    run.check_digests = resident_digests(system)

    metrics = {}
    for m in (run.spec.per_layer if trace else run.spec.end_to_end):
        value = load_reader(m["name"], run.spec.root)(run)
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = run.device
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": False, "attempted": len(due),
              "failed": sum(not r.ok for r in due),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    return result

