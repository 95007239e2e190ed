"""One run of one cell: set-up, warm steps, the measured window, and after
it the peak, the traced numbers and the reference check.

It enters the program only where a user does — ``bf.init``,
``bf.set_topology``, ``bf.Distributed*Optimizer``, ``bf.make_train_step``,
and ``opt.lower_last_fused_hlo`` / ``metrics.peek`` for static counts — and
knows no cell, model or metric by name: those come from the cell's data
files, the job builder its configuration names and the readers in
``layer_metrics/``.
"""

import contextlib
import importlib.util
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

import bluefog_tpu as bf
from bluefog_tpu import metrics as bf_metrics
from bluefog_tpu.collective.plan import schedule_from_dynamic

from benchmarks.harness import cells, hlo_text, reference, scopes, trace_reduce

STEPS_PER_BLOCK = 10   # a user who logs (and so syncs) every 10 steps
WARM_STEPS = 3         # PR 21: the second call of a fused step compiles again
TRACED_BLOCKS = 2
N_BATCHES = 4          # resident, cycled: the repo has no input pipeline
CHECK_STEPS = 3        # every round of a period-2 schedule mixes unequal workers

COUNTERS = ("bluefog.recompiles", "bluefog.wire_bytes", "bluefog.gossip.rounds")


class Spans:
    """The harness's own spans, in memory: (name, start, end) on
    ``time.perf_counter``. ``origin`` is the process's start."""

    def __init__(self, origin):
        self.origin = origin
        self.items = []

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.items.append((name, t0, time.perf_counter()))

    def seconds(self, name):
        return sum(t1 - t0 for n, t0, t1 in self.items if n == name)


class CompileEvents:
    """What jax itself reports: every program compiled or loaded
    (``backend_compile_duration`` wraps both) and the persistent cache's
    hits and misses. jax keeps a listener for the life of the process, so
    there is one of these (``compile_events()``), read by differences."""

    def __init__(self):
        self.programs = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event, _seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def snapshot(self):
        return {"programs": self.programs, "hits": self.hits, "misses": self.misses}


_events = None


def compile_events():
    global _events
    if _events is None:
        _events = CompileEvents()
    return _events


def _counter_values():
    """The host registry's counts; a series nothing has written yet (the
    wire accounting only runs under BLUEFOG_METRICS=1) reads 0."""
    out = {}
    for name in COUNTERS:
        series = bf_metrics.peek(name)
        out[name] = float(series.value) if series is not None else 0.0
    return out


def _load_module(name, path):
    """A job builder or a reader, found by the name a data file gives."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_job(cell):
    job = cell.config["job"]
    module = _load_module(f"benchmarks.jobs.{job}", cells.job_path(job))
    return module.Job(cell.config, cell.traffic)


def load_reader(metric):
    return _load_module(
        f"benchmarks.layer_metrics.{metric}", cells.reader_path(metric)
    ).read


_GRAPHS = {"exp2": "ExponentialTwoGraph", "ring": "RingGraph"}


def build_optimizer(traffic, tx):
    """The optimizer a traffic mix names, with its topology, schedule and
    wire set the way a user sets them."""
    n = bf.size()
    kind = traffic["optimizer"]
    graph = traffic["topology"] and getattr(bf.topology, _GRAPHS[traffic["topology"]])
    if kind == "hierarchical":
        opt = bf.DistributedHierarchicalNeighborAllreduceOptimizer(tx)
        bf.set_machine_topology(
            (graph or bf.topology.RingGraph)(bf.machine_size()), is_weighted=True
        )
    elif kind == "gradient_allreduce":
        opt = bf.DistributedGradientAllreduceOptimizer(tx)
    else:
        opt = bf.DistributedNeighborAllreduceOptimizer(tx)
        if graph:
            bf.set_topology(graph(n), is_weighted=True)
    if traffic["schedule"] == "one_peer_exp2" and n > 1:
        exp2 = bf.topology.ExponentialTwoGraph(n)
        opt.schedule = schedule_from_dynamic(
            n, lambda r: bf.topology.GetDynamicOnePeerSendRecvRanks(exp2, r)
        )
    if traffic["wire"]:
        opt.compression = traffic["wire"]
    return opt


def peak_bytes():
    """Peak device memory of the process so far on the fullest chip:
    ``peak_bytes_in_use`` (the arrays) plus ``peak_bytes_reserved`` (the
    scratch a loaded program sets aside: on the v5e the first does not hold
    the second; their sum is what ``memory_analysis()`` gives for a step,
    PR 22). ``None`` where the backend reports no statistics."""
    peaks = []
    for d in bf.get_context().devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(
                stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)
            )
    return max(peaks) if peaks else None


@jax.jit
def _all_finite(tree):
    return jnp.stack(
        [jnp.isfinite(leaf).all() for leaf in jax.tree_util.tree_leaves(tree)]
    ).all()


class Run:
    """What a per-layer reader is given. Everything a reader may need is an
    attribute here; a reader that finds nothing to read returns ``None``."""

    def __init__(self, cell, n, job, peaks, spans):
        self.cell, self.n, self.job = cell, n, job
        self.peaks, self.spans = peaks, spans
        self.trace = None          # trace_reduce.Trace of the traced steps
        self.traced_steps = 0
        self.hlo = None            # hlo_text.HloIndex of the compiled step
        self.warm_step_s = []      # each warm call, ended by block_until_ready
        self.block_s = []          # each block of the window
        self.dispatch_s = []       # each train_step(...) call's time to return
        self.compile_events = {}   # phase -> CompileEvents.snapshot()
        self.counters = {}         # bluefog counter deltas over the window
        self._ms_by_kind = None

    def device_ms_by_kind(self):
        """Device time per step and chip, in ms, of each kind of
        instruction (``hlo_text``'s kinds, and ``unresolved`` for an event
        whose name the compiled step does not hold): each event's own
        time, summed, meaned over the chips. ``None`` without a trace or
        the step's HLO."""
        if self.trace is None or self.hlo is None:
            return None
        if self._ms_by_kind is None:
            totals = dict.fromkeys(
                (hlo_text.MATMUL_CONV, hlo_text.COLLECTIVE, hlo_text.MOSAIC,
                 hlo_text.OTHER, "unresolved"), 0.0,
            )
            scale = 1e6 * len(self.trace.devices) * self.traced_steps
            for device in self.trace.devices:
                step_ops = device.ops_of(self.hlo.module)
                for op, ns in trace_reduce.self_times(step_ops):
                    totals[self.hlo.kind(op.name) or "unresolved"] += ns / scale
            self._ms_by_kind = totals
        return self._ms_by_kind


def run_cell(cell, seed, seconds, trace, spans, info, devices=None):
    """-> the result line (a dict). ``info(dict)`` is called with the
    earlier lines. ``devices`` is for the CPU rehearsal in
    benchmarks/tests; the command passes none and gets every chip."""
    events = compile_events()
    at_start = events.snapshot()
    os.environ.update(cell.traffic["env"])
    with spans.span("init"):
        bf.init(devices=devices, nodes_per_machine=cell.traffic["nodes_per_machine"])
    n = bf.size()
    device = bf.get_context().devices[0]
    peaks = cells.load_peaks(device.device_kind) if device.platform == "tpu" else None
    mesh = bf.get_context().mesh
    axis = mesh.axis_names[0]
    stacked = NamedSharding(mesh, PartitionSpec(axis))
    job = load_job(cell)
    run = Run(cell, n, job, peaks, spans)
    tx = reference.make_tx(cell.config["optimizer"])

    def _weights(key):
        return jax.tree_util.tree_map(
            lambda t: jnp.broadcast_to(t[None], (n,) + t.shape), job.init(key)
        )

    make_weights = jax.jit(_weights, out_shardings=stacked)
    make_batches = jax.jit(
        lambda key: tuple(
            job.make_batch(k, n) for k in jax.random.split(key, N_BATCHES)
        ),
        out_shardings=stacked,
    )
    k_weights, k_data = jax.random.split(jax.random.PRNGKey(seed))

    with spans.span("weights"):
        params, aux = jax.block_until_ready(make_weights(k_weights))
    n_params = sum(l.size // n for l in jax.tree_util.tree_leaves(params))
    if n_params != cell.config["n_params"]:
        raise RuntimeError(
            f"{cell.config_name}: the model has {n_params} parameters, its "
            f"configuration states {cell.config['n_params']}"
        )
    with spans.span("data"):
        batches = jax.block_until_ready(make_batches(k_data))

    opt = build_optimizer(cell.traffic, tx)
    fused = bf.make_train_step(opt, job.loss_fn, has_aux=job.has_aux)

    def step(carry, batch):
        if job.has_aux:
            p, s, a = carry
            p, s, (loss, a) = fused(p, s, a, *batch)
            return (p, s, a), loss
        p, s = carry
        p, s, loss = fused(p, s, *batch)
        return (p, s), loss

    def start(params, aux):
        return (params, opt.init(params)) + ((aux,) if job.has_aux else ())

    carry = start(params, aux)
    del params, aux
    dispatched = 0
    with spans.span("warm_steps"):
        for _ in range(WARM_STEPS):
            t0 = time.perf_counter()
            carry, loss = step(carry, batches[dispatched % N_BATCHES])
            jax.block_until_ready((carry, loss))
            run.warm_step_s.append(time.perf_counter() - t0)
            dispatched += 1
    # each warm step was synced, so this is what ONE step needs; in the
    # window the host runs ahead and every step in flight holds its outputs
    step_peak = peak_bytes()
    run.compile_events["setup"] = {
        k: v - at_start[k] for k, v in events.snapshot().items()
    }

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # TraceAnnotations only: a python
        # tracer would slow the very host work the gaps are attributed to
        with spans.span("traced_steps"):
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                for _ in range(TRACED_BLOCKS):
                    for _ in range(STEPS_PER_BLOCK):
                        with jax.profiler.TraceAnnotation(trace_reduce.DISPATCH):
                            carry, loss = step(carry, batches[dispatched % N_BATCHES])
                        dispatched += 1
                    with jax.profiler.TraceAnnotation(trace_reduce.SYNC):
                        jax.block_until_ready(loss)
            finally:
                jax.profiler.stop_trace()
        run.traced_steps = TRACED_BLOCKS * STEPS_PER_BLOCK

    # -- the window: steps back to back, one sync per block ------------------
    before, counters_before = events.snapshot(), _counter_values()
    losses, raised = [], 0
    setup_s = time.perf_counter() - spans.origin
    t_start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        if t0 - t_start >= seconds:
            break
        try:
            for _ in range(STEPS_PER_BLOCK):
                td = time.perf_counter()
                carry, loss = step(carry, batches[dispatched % N_BATCHES])
                run.dispatch_s.append(time.perf_counter() - td)
                dispatched += 1
                losses.append(loss)
            jax.block_until_ready(loss)
        except Exception as e:  # a failed dispatch ends the window, counted
            info({"window_error": repr(e)})
            raised += 1
            break
        run.block_s.append(time.perf_counter() - t0)
    window_s = sum(run.block_s)
    spans.items.append(("window", t_start, t_start + window_s))
    after = events.snapshot()
    run.compile_events["window"] = {k: after[k] - before[k] for k in after}
    run.counters = {
        k: v - counters_before[k] for k, v in _counter_values().items()
    }

    # -- after the window ----------------------------------------------------
    memory_peak = peak_bytes()
    steps = len(run.block_s) * STEPS_PER_BLOCK
    losses_np = (
        np.asarray(jnp.stack(losses), np.float64) if losses
        else np.zeros((0, n))
    )
    failed = raised + int((~np.isfinite(losses_np).all(axis=1)).sum())
    finite_end = bool(_all_finite(carry[0]))
    if trace:
        with spans.span("hlo"):
            run.hlo = hlo_text.HloIndex(opt.lower_last_fused_hlo(
                *carry, *batches[0]
            ))
    del carry

    with spans.span("reference"):
        p_sys, sys_losses = start(*make_weights(k_weights)), []
        first_round = dispatched
        for k in range(CHECK_STEPS):
            p_sys, loss = step(p_sys, batches[k % N_BATCHES])
            sys_losses.append(loss)
            dispatched += 1
        # the program's parameters wait on the host while the reference
        # runs, and its optimizer state is dropped: the span then holds the
        # reference's parameters, momentum and gradients (12 B a parameter)
        # and not a fourth copy beside them, so the check, which is outside
        # the window and after both peaks are read, bounds no configuration
        p_shardings = jax.tree_util.tree_map(lambda t: t.sharding, p_sys[0])
        p_sys = jax.device_get(p_sys[0])
        p_ref, ref_losses = reference.run_reference(
            job, tx, cell.traffic, axis, *make_weights(k_weights), batches,
            CHECK_STEPS, first_round=first_round,
        )
        p_sys = jax.device_put(p_sys, p_shardings)
        agrees, report = reference.compare(
            sys_losses, ref_losses, p_sys, p_ref, make_weights(k_weights)[0],
            cells.tolerance(cell),
        )
        del p_sys, p_ref
    compiled_in_window = (
        run.compile_events["window"]["programs"]
        + int(run.counters["bluefog.recompiles"])
    )
    correct = bool(
        agrees and finite_end and failed == 0 and steps > 0
        and compiled_in_window == 0
    )

    units = steps * job.units_per_worker_step * n
    throughput = units / window_s / n if window_s else 0.0
    e2e = {
        "throughput_per_chip": throughput,
        "peak_hbm_gib": (step_peak or 0) / 2 ** 30,
        "setup_s": setup_s,
    }
    if peaks is not None:
        e2e["mfu"] = throughput * job.flops_per_unit / peaks["bf16_flops_per_s"]

    info({
        "cell": cell.name, "config": cell.config_name,
        "traffic": cell.traffic_name, "seed": seed, "workers": n,
        "unit": cell.config["unit"], "n_params": n_params,
        "flops_per_unit": job.flops_per_unit,
        "spans_s": {
            name: round(spans.seconds(name), 4)
            for name in dict.fromkeys(s[0] for s in spans.items)
        },
        "warm_step_s": [round(t, 4) for t in run.warm_step_s],
        "block_s": [round(t, 4) for t in run.block_s],
        "loss_first_last": (
            [losses_np[0].tolist(), losses_np[-1].tolist()] if steps else None
        ),
        "compile_events": run.compile_events,
        "counters_window": run.counters,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "reference": report, "finite_end": finite_end,
        "hlo": run.hlo.summary() if run.hlo else None,
        "end_to_end": e2e,
    })

    device_line = {
        "platform": device.platform, "kind": device.device_kind, "count": n,
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": correct, "attempted": len(losses) + raised, "failed": failed,
    }
    if not trace:
        result["metrics"] = {
            name: {"value": e2e[name], "unit": cell.units[name]}
            for name in cell.end_to_end if name in e2e
        }
    else:
        try:
            with spans.span("trace_reduce"):
                run.trace = trace_reduce.load_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = {}
        for name in cell.per_layer:
            value = load_reader(name)(run)
            if value is not None:
                result["metrics"][name] = {"value": value, "unit": cell.units[name]}
        device_line["busy_s"] = run.trace.busy_s()
        device_line["window_s"] = run.trace.window_s()
        result["breakdown"] = {
            # seconds per step; instructions of one jax operation in
            # every layer are one entry (hlo_text.HloIndex.family)
            "device_ops": run.trace.top_ops(
                10, per_step=run.traced_steps,
                label=lambda op: run.hlo.family(op.name),
            ),
            # seconds over the whole traced window
            "idle_gaps": run.trace.idle_by_host_span(5),
        }
        info({
            "traced_steps": run.traced_steps,
            "device_ms_per_step_by_kind": run.device_ms_by_kind(),
            # each entry against the kernels it names, one that names
            # none against all Mosaic time (scopes.kernel_roofline)
            "kernels": {
                name: scopes.kernel_roofline(run, cost)
                for name, cost in job.kernel_costs().items()
            } if peaks else None,
        })
    result["device"] = device_line
    # each number `correct` was decided by, beside its limit: [read, limit]
    # (a non-finite reading as text: the line has to stay JSON)
    result["compared"] = {
        name: [read if np.isfinite(read) else repr(read), limit]
        for name, read, limit in (
            ("loss_abs_err", report["loss_abs_err"], report["loss_abs_tol"]),
            ("update_l2_err", np.max(report["update_l2_err"]).item(),
             report["update_l2_tol"]),
            ("failed_steps", failed, 0),
            ("compiled_in_window", compiled_in_window, 0),
            ("window_steps_at_least", steps, 1),
            ("parameters_finite", int(finite_end), 1),
        )
    }
    bf.shutdown()
    return result
