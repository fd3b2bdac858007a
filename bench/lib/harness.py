"""One run of one cell: set-up, the measured window, the check.

The entry the window drives is the program's trainer path on the device
data plane: ``RoundEngine.run_device`` called chunk after chunk, as
``launch/train.py::run --data-plane device --rounds-per-step T`` does.
Each chunk's ``(T,)`` losses are fetched one chunk late, so the host never
stalls the device.

Set-up is everything before the window's first dispatch: the process's
start, the weights (made on the device from the seed in one jitted call),
the corpus (made on the host from the seed and uploaded once), the engine,
and the first ``CHECK_CHUNKS`` chunks through the window's own call — the
first of which compiles or loads the superstep from the persistent cache.
Those chunks are the ones the reference follows: their losses and the
server after the first and the third are read before the next chunk takes
the state. The same engine and state then run the window: whole chunks
from the first dispatch to ``block_until_ready`` on the last, for at least
``seconds``. Compilations inside the window are counted; a run with any
is not correct.

After the window the peak device memory is read, the program's state is
freed, and the plain reference runs the checked chunks from the same
weights and draws (``reference``); ``check`` compares them."""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from lib import cell as cell_mod
from lib import check, counting, reference, replay, trace
from lib.peaks import chip_peaks

SPANS = ("window", "dispatch", "fetch_losses", "drain")
CHECK_CHUNKS = 3    # the chunks set-up drives and the reference follows
GIB = float(1 << 30)


class DeviceError(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Facts:
    """What a per-layer metric reader may read about a traced run."""
    space: dict
    window_ns: tuple            # trace clock (start, end)
    window_s: float             # host clock
    rounds: int
    chunks: int
    host_chunk_s: list
    live_flops: float           # None when the live-step replay disagreed
    peaks: dict
    agg_bytes_per_round: float
    bench: Path

    def metric(self, name: str):
        return cell_mod.metric_reader(name, self.bench)(self)


def _program(cell):
    """The program's model config, the loss it trains and its init's
    shapes (to check the benchmark's weights have the trainer's layout)."""
    from repro.configs import get_config
    from repro.models import model
    prog = cell.config["program"]
    pcfg = dataclasses.replace(get_config(prog["arch"]), **prog["overrides"])

    def lfn(p, b):
        return model.loss_fn(p, pcfg, b)
    return pcfg, lfn, model.init_params


def _same_layout(weights, shapes):
    import jax
    a = jax.tree_util.tree_structure(weights)
    b = jax.tree_util.tree_structure(shapes)
    if a != b:
        raise ValueError(f"weights do not have the trainer's layout:\n{a}\n{b}")
    for w, s in zip(jax.tree_util.tree_leaves(weights),
                    jax.tree_util.tree_leaves(shapes)):
        if w.shape != s.shape or w.dtype != s.dtype:
            raise ValueError(f"leaf {w.shape}/{w.dtype} vs {s.shape}/{s.dtype}")


def _host_leaves(spec, bufs) -> list:
    """The server's leaves, on the host, from the engine's flat buffers."""
    flat = [np.asarray(b) for b in bufs]
    out = []
    for shape, b, off in zip(spec.shapes, spec.bucket_of, spec.offsets):
        if spec.shards(b) != 1:
            raise ValueError("model-sharded buckets are not read here")
        size = int(np.prod(shape))
        out.append(flat[b][off:off + size].reshape(shape))
    return out


class _CompileCounter:
    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.n += 1


@dataclasses.dataclass
class Context:
    """A cell loaded and the program imported, on the device it runs on."""
    cell: object
    ref: object
    device: object
    n_devices: int
    pcfg: object
    lfn: object
    init_params: object


def prepare(workload: str, bench: Path = cell_mod.BENCH, *,
            allow_cpu: bool = False, log=sys.stderr) -> Context:
    """Load the cell, import the program, look for the chips, and point
    JAX's persistent compilation cache at its place in the checkout."""
    cell = cell_mod.load(workload, bench)
    src = str(cell.root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    from repro.utils.compile_cache import setup_compile_cache

    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", file=log)
    if d0.platform != "tpu" and not allow_cpu:
        raise DeviceError(f"needs a TPU; JAX found {d0.platform!r}")
    if len(devices) < cell.chips:
        raise DeviceError(f"{cell.name} needs {cell.chips} chips; JAX found "
                          f"{len(devices)}")
    cache = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"compile cache: {cache}", file=log)
    pcfg, lfn, init_params = _program(cell)
    ref = reference.Reference(reference.load_module(cell.ref_path),
                              cell.config, cell.traffic)
    return Context(cell=cell, ref=ref,
                   device=d0, n_devices=len(devices), pcfg=pcfg, lfn=lfn,
                   init_params=init_params)


def make_engine(ctx: Context, weights):
    """The program's round engine for the cell's traffic (the speed law is
    the same for every seed, so one engine serves every seed)."""
    import jax.numpy as jnp
    from repro.core import FavasConfig, RoundEngine
    tr = ctx.cell.traffic
    fcfg = FavasConfig(n_clients=tr["n_clients"], s_selected=tr["s"],
                       local_steps=tr["K"], eta=tr["eta"],
                       reweight=tr["reweight"], quant_bits=tr["quant_bits"],
                       slow_fraction=tr["slow_fraction"],
                       lam_fast=tr["lam_fast"], lam_slow=tr["lam_slow"])
    return RoundEngine(weights, fcfg, ctx.lfn,
                       lambdas=jnp.asarray(replay.speed_law(tr)))


@dataclasses.dataclass
class Inputs:
    """What the benchmark makes from a seed: weights, corpus, key."""
    seed: int
    weights: object
    tokens: np.ndarray
    domains: np.ndarray
    corpus: object


def make_inputs(ctx: Context, seed: int) -> Inputs:
    import jax
    from repro.data.device_corpus import make_lm_device_corpus
    from lib.corpus import make_lm_corpus
    tr, hf = ctx.cell.traffic, ctx.cell.config
    wkey = reference.weights_key(seed)
    weights = ctx.ref.weights(wkey)
    _same_layout(weights, jax.eval_shape(
        lambda k: ctx.init_params(k, ctx.pcfg), wkey))
    n = tr["n_clients"]
    tokens, domains = make_lm_corpus(hf["vocab_size"], tr["corpus_tokens"],
                                     max(n, 2), seed)
    corpus = make_lm_device_corpus(tokens, domains, n, tr["batch"], tr["seq"])
    return Inputs(seed, weights, tokens, domains, corpus)


def checked_chunks(ctx: Context, engine, inp: Inputs):
    """Start the engine from the seed's weights and drive it through the
    chunks the check follows, by the window's own call. Returns the state
    and the program's readings: every round's loss, and the server's
    leaves (on the host) after the first and the last checked chunk."""
    from lib import replay as rp
    T = ctx.cell.traffic["rounds_per_chunk"]
    state = engine.init_state(inp.weights, rp.state_key(inp.seed))
    inp.weights = None
    prog = {"losses": [], "servers": {}}
    for c in range(1, CHECK_CHUNKS + 1):
        state, m = engine.run_device(state, inp.corpus, T)
        prog["losses"] += [float(x) for x in np.asarray(m["loss"])]
        if c in (1, CHECK_CHUNKS):
            prog["servers"][c] = _host_leaves(engine.spec, state.server)
    return state, prog


def reference_run(ctx: Context, inp: Inputs, *, mode: str = "highest",
                  fault=None, ref=None) -> tuple:
    """The plain reference (``ref``, by default the cell's) over the
    checked chunks from the seed's weights (made again), in ``mode``;
    returns (its readings, the initial leaves, the leaf names)."""
    import jax
    from lib.corpus import start_bounds
    tr = ctx.cell.traffic
    ref = ref or ctx.ref
    w0 = ref.weights(reference.weights_key(inp.seed))
    names = reference.leaf_paths(w0)
    lo, span = start_bounds(inp.domains, tr["n_clients"], tr["seq"])
    out = ref.run(w0, inp.tokens, lo, span, replay.speed_law(tr),
                      inp.seed, n_chunks=CHECK_CHUNKS, keep=(1, CHECK_CHUNKS),
                      mode=mode, fault=fault)
    return out, jax.tree_util.tree_leaves(w0), names


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        t_start: float, bench: Path = cell_mod.BENCH, allow_cpu: bool = False,
        trace_dump: str | None = None, log=sys.stderr) -> dict:
    ctx = prepare(workload, bench, allow_cpu=allow_cpu, log=log)
    import jax
    cell, ref, d0 = ctx.cell, ctx.ref, ctx.device
    tr, hf = cell.traffic, cell.config
    n, s, K, T = tr["n_clients"], tr["s"], tr["K"], tr["rounds_per_chunk"]
    lambdas = replay.speed_law(tr)

    # --- set-up -----------------------------------------------------------
    inp = make_inputs(ctx, seed)
    engine = make_engine(ctx, inp.weights)
    state, prog = checked_chunks(ctx, engine, inp)
    corpus = inp.corpus
    inp.corpus = None

    # --- the window ---------------------------------------------------------
    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    tmp = tempfile.TemporaryDirectory() if traced else None
    if traced:
        jax.profiler.start_trace(tmp.name)
    window_losses, host_s, chunks, pending = [], [], 0, None
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with jax.profiler.TraceAnnotation("window"):
        while True:
            with jax.profiler.TraceAnnotation("dispatch"):
                a = time.perf_counter()
                state, m = engine.run_device(state, corpus, T)
                host_s.append(time.perf_counter() - a)
            chunks += 1
            if pending is not None:
                with jax.profiler.TraceAnnotation("fetch_losses"):
                    window_losses += list(np.asarray(pending["loss"]))
            pending = m
            if time.perf_counter() - t0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("drain"):
            jax.block_until_ready(state)
    t1 = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    jax.monitoring.unregister_event_duration_listener(counter)
    window_losses += list(np.asarray(pending["loss"]))
    window_s = t1 - t0
    rounds = chunks * T
    stats = d0.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    counters = np.asarray(state.counters)
    del state, corpus, engine, m, pending
    gc.collect()

    # live steps of the window, replayed from the state key
    live, q_end = replay.live_steps(replay.state_key(seed), lambdas, K=K, s=s,
                                    n_rounds=(CHECK_CHUNKS + chunks) * T)
    replay_ok = bool(np.array_equal(q_end, counters))
    flops_tok = counting.train_flops_per_token(
        ref.module.forward_flops_per_token(hf, tr["seq"], counting))
    live_window = int(np.sum(live[CHECK_CHUNKS * T:]))
    live_flops = (live_window * tr["batch"] * tr["seq"] * flops_tok
                  if replay_ok else None)

    # --- the check ----------------------------------------------------------
    t_ref = time.perf_counter()
    ref_out, w0, names = reference_run(ctx, inp)
    t_cmp = time.perf_counter()
    numbers = check.compare(prog, ref_out, w0, names)
    cmp_s = time.perf_counter() - t_cmp
    correct, rows = check.judge(numbers, cell.limits)
    ref_s = time.perf_counter() - t_ref
    finite = [x for x in prog["losses"] + window_losses if math.isfinite(x)]
    failed = len(prog["losses"]) + len(window_losses) - len(finite)
    correct = correct and failed == 0 and counter.n == 0

    print(f"window: {chunks} chunks x {T} rounds in {window_s:.3f} s; "
          f"{counter.n} compilations inside; live steps {live_window}; "
          f"replay {'agrees' if replay_ok else 'DISAGREES'} with the "
          f"engine's counters; reference {ref_s:.1f} s (comparison "
          f"{cmp_s:.1f} s of it)", file=log)
    print(f"trained tokens/s: {live_window * tr['batch'] * tr['seq'] / window_s:.1f}"
          f" live, {rounds * n * K * tr['batch'] * tr['seq'] / window_s:.1f}"
          f" computed", file=log)
    print(f"worst leaves: first_change {numbers['first_change_leaf']}, "
          f"change_3 {numbers['change_3_leaf']}; left out "
          f"{numbers['left_out']}", file=log)

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": ctx.n_devices, "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(prog["losses"]) + rounds,
              "failed": failed, "window_compiles": counter.n}
    if not traced:
        metrics = {"rounds_per_s": {"value": rounds / window_s,
                                    "unit": "rounds/s"},
                   "peak_hbm_gib": {"value": peak / GIB, "unit": "GiB"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        path = next(Path(tmp.name).rglob("*.xplane.pb"))
        space = trace.load(str(path), host_spans=SPANS)
        tmp.cleanup()
        lo_ns, hi_ns = trace.window_bounds(space)
        facts = Facts(space=space, window_ns=(lo_ns, hi_ns), window_s=window_s,
                      rounds=rounds, chunks=chunks, host_chunk_s=host_s,
                      live_flops=live_flops,
                      peaks=chip_peaks(d0.device_kind) if d0.platform == "tpu"
                      else None,
                      agg_bytes_per_round=counting.agg_bytes_per_round(
                          sum(x.size for x in w0), s),
                      bench=bench)
        metrics = {}
        for m_ in cell.per_layer:
            v = facts.metric(m_["name"])
            if v is not None:
                metrics[m_["name"]] = {"value": v, "unit": m_["unit"]}
        if trace.device_planes(space):
            device["busy_s"] = (trace.busy_share(space, lo_ns, hi_ns)
                                * (hi_ns - lo_ns) / 1e9)
        device["window_s"] = (hi_ns - lo_ns) / 1e9
        result["breakdown"] = {
            "device_ops": trace.top_ops(space, lo_ns, hi_ns),
            "idle_gaps": trace.idle_gaps(space, lo_ns, hi_ns)}
        if trace_dump:
            Path(trace_dump).parent.mkdir(parents=True, exist_ok=True)
            Path(trace_dump).write_text(json.dumps(
                {"summary": trace.summary(space),
                 "excerpt": trace.excerpt(space, lo_ns)}))
    result["metrics"] = metrics
    result["device"] = device
    result["compared"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        print(f"compared {k}: {v!r} (limit {lim!r})", file=log)
    return result
