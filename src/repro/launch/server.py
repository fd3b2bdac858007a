"""FavasAsyncServer: the FAVAS aggregator as a transport actor
(docs/architecture.md §11).

This is the simulated-clock round loop of ``core/fl_sim.py`` re-expressed
as an event-driven server, with the engine's flat-buffer round step
(``round_engine.aggregate_and_reset``) as the aggregation core. Protocol,
per round ``r`` (cadence ``round_dur``):

1. ``tick``  server -> every client: carry the round index and a
   ``polled`` flag. Clients advance their integer-tick credit clock and run
   that many local SGD steps; polled clients then push their update.
2. ``update`` client -> server (the UNRELIABLE class — may be dropped or
   duplicated by the fault layer; clients retry with exponential backoff
   until ``ack``-ed): flat parameter buckets + the client's local-step
   count ``q`` (the eq. 3 alpha numerator).
3. Harvest: when all polled updates are admitted, or at
   ``harvest_frac * round_dur``, the server aggregates the admitted set
   with eq. 3 de-biasing (alpha_i = max(q_i, 1), stochastic reweight) and
   FAVAS line 10, normalizing by ``(n_admitted + 1)`` — equal to the
   simulator's ``(s + 1)`` whenever every polled client delivers, and a
   graceful contraction toward the current iterate when faults thin the
   poll. ``reset`` goes to each ADMITTED client (new params, q -> 0);
   un-admitted stragglers keep training uninterrupted, exactly like the
   simulator's unselected clients.

Key-chain equivalence: the server draws the round selection from the SAME
chain as ``fl_sim`` — ``rkey, k_sel, k_q = jax.random.split(rkey, 3)``
with ``rkey = PRNGKey(seed)``, selection via
``sampler.sample_selection_indices(k_sel, n, s)`` — so the selection
stream is bit-identical to the simulated baseline (asserted in
tests/test_async_server.py). ``k_q`` is split even when unused, keeping
the chain aligned; with ``quant_bits > 0`` it keys the per-client LUQ
encode of PENDING updates (below).

Quantized admission (``quant_bits > 0``): an admitted update is
immediately re-encoded with ``kernels.ops.cold_requant_rows`` under
``fold_in(k_q, client)`` and held BETWEEN admission and harvest as codes +
scales — so in-flight progress never sits at full precision in server
memory, and the pending set is part of the checkpointable state:
``checkpoint_state()`` / ``save()`` round-trip the flat buckets, the rng
key chain, and every pending entry's codes + scales through
``checkpointing.ckpt.save_engine_checkpoint`` bit-exactly (the PR 7
checkpointing gap, tests/test_async_server.py::test_server_checkpoint_*).

Durability (``wal_dir`` set; docs/architecture.md §12): every round start,
every admitted update (its wire-exact entry — codes + scales when
``quant_bits > 0``), and every round close is appended to a crash-safe
write-ahead log (``checkpointing/wal.py``) BEFORE the effect is
acknowledged, and every ``ckpt_every`` closed rounds the full server state
snapshots atomically (tmp + fsync + rename) and the WAL rotates. A killed
server recovers as snapshot + WAL replay (:func:`recover_server`) —
selection re-derives from the logged key chain, the pending set rebuilds
bit-exactly from the admit records, and closes re-run the deterministic
aggregation. Admission is EXACTLY-ONCE across restarts: clients stamp
``(round, seq)`` on every push, the dedup ledger rides in the WAL/snapshot
with the admits, and a retransmit of an already-logged update after
recovery is acked-but-ignored. On restart the server announces a
``recover`` hello (epoch + current round) and re-broadcasts the open
round's ticks; clients treat ticks/resets idempotently by round, so the
recovered trajectory's buckets are bit-exact vs an uninterrupted run
(tests/test_chaos_recovery.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.checkpointing import wal
from repro.comms.transport import Actor, TransportAPI
from repro.core import round_engine, sampler
from repro.kernels import ops as kops

SERVER_ID = "server"

#: LUQ code widths the pending-update codec supports (0 = raw admission)
SUPPORTED_QUANT_BITS = (0, 2, 4, 8)


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Deployment config. Defaults mirror ``fl_sim.SimConfig`` semantics;
    ``round_dur`` is virtual seconds under InProcTransport and wall seconds
    under ProcEndpoint (the protocol only ever sees the ratio of latencies
    to ``round_dur``, which is why one config drives both)."""
    n_clients: int = 8
    s_selected: int = 2
    K: int = 10
    eta: float = 0.2
    batch_size: int = 32
    rounds: int = 20
    round_dur: float = 7.0           # fl_sim SERVER_WAIT + SERVER_INTERACT
    harvest_frac: float = 0.9        # harvest deadline, fraction of round
    eval_every_rounds: int = 0       # 0: record only the final model
    quant_bits: int = 0              # LUQ-encode pending updates (0: raw)
    barrier_timeout: float = 120.0   # max wait for client hellos at startup
    fast_step_time: float = 2.0
    slow_step_time: float = 16.0
    slow_fraction: float = 1.0 / 3.0
    permute_speeds: bool = True
    seed: int = 0

    def __post_init__(self):
        if not 0 < self.harvest_frac <= 1.0:
            raise ValueError(f"harvest_frac must be in (0, 1], got "
                             f"{self.harvest_frac}")
        if self.round_dur <= 0:
            raise ValueError(f"round_dur must be > 0, got {self.round_dur}")
        if self.n_clients < 1:
            raise ValueError(f"n_clients must be >= 1, got {self.n_clients}")
        if self.s_selected > self.n_clients:
            raise ValueError("s_selected > n_clients")
        if self.quant_bits not in SUPPORTED_QUANT_BITS:
            raise ValueError(
                f"quant_bits must be one of {SUPPORTED_QUANT_BITS} (the LUQ "
                f"codec's supported widths), got {self.quant_bits}")

    def step_times(self) -> np.ndarray:
        """Per-client step times, IDENTICAL to fl_sim's ``_step_times``
        draw (same rng consumption) so tick streams line up."""
        from repro.core.fl_sim import _step_times
        return _step_times(self, np.random.default_rng(self.seed))


class FavasAsyncServer(Actor):
    """The aggregator actor. Runs unmodified on InProcTransport (virtual
    clock — the deterministic test substrate) and ProcEndpoint (real
    processes). ``eval_fn(params_tree) -> float`` is optional."""

    node_id = SERVER_ID

    def __init__(self, cfg: AsyncConfig, params0,
                 eval_fn: Optional[Callable] = None,
                 client_ids: Optional[List[str]] = None, *,
                 wal_dir: Optional[str] = None, ckpt_every: int = 0,
                 wal_fsync: bool = True, chaos=None):
        self.cfg = cfg
        n = cfg.n_clients
        self.client_ids = list(client_ids) if client_ids is not None \
            else [f"client{i}" for i in range(n)]
        if len(self.client_ids) != n:
            raise ValueError("client_ids length != n_clients")
        self._row = {c: i for i, c in enumerate(self.client_ids)}
        self.spec = round_engine.make_flat_spec(params0, n_clients=n)
        self.srv_f = round_engine.flatten_tree(self.spec, params0)
        self.cli_f = round_engine.stack_server_rows(self.spec, self.srv_f, n)
        self.ini_f = round_engine.stack_server_rows(self.spec, self.srv_f, n)
        self.rkey = jax.random.PRNGKey(cfg.seed)
        self.eval_fn = eval_fn
        self.round = -1                  # index of the OPEN round
        self._open = False
        self._k_q = None                 # this round's quant key
        self._polled: List[str] = []
        self.pending: Dict[str, dict] = {}
        # exactly-once dedup ledger: client -> (round, seq) of its LAST
        # admitted (durably logged) update — WAL/snapshot-recorded, so a
        # retransmit after recovery is acked-but-ignored
        self.ledger: Dict[str, Tuple[int, int]] = {}
        self.epoch = 0                   # number of server incarnations
        # equivalence logs + operational stats (tests read these)
        self.selection_log: List[tuple] = []
        self.alpha_log: List[dict] = []
        self.staleness: List[int] = []   # q of each ADMITTED update
        self.curves = {"round": [], "accuracy": []}
        self.client_logs: Dict[str, list] = {}
        self.stats = {"rounds": 0, "admitted": 0, "late": 0, "short_polls": 0,
                      "resets": 0, "rejoins": 0, "byes": 0, "dedup": 0,
                      "recoveries": 0}
        self._stopping = False
        self._ready: set = set()
        self._started = False
        # durability layer (docs/architecture.md §12)
        self.wal_dir = wal_dir
        self.ckpt_every = int(ckpt_every)
        self._wal = wal.WalWriter(wal_dir, fsync=wal_fsync) \
            if wal_dir else None
        self._chaos = chaos              # comms.faults.ServerCrashSwitch
        self._recovered = False
        self._last_close: Optional[dict] = None

    # -- actor contract ------------------------------------------------------

    def on_start(self, api: TransportAPI) -> None:
        if self._recovered:
            self._resume(api)
            return
        # hello barrier: clients check in before round 0 — on the proc
        # transport a child spends seconds importing jax and warming up its
        # SGD jit, and starting the cadence early would turn the first
        # rounds into spurious short polls. The fallback timer bounds the
        # wait so a never-arriving client can't wedge startup.
        api.set_timer("barrier", self.cfg.barrier_timeout)

    def _begin(self, api: TransportAPI) -> None:
        if self._started:
            return
        self._started = True
        api.cancel_timer("barrier")
        api.set_timer("round", 0.0)

    def on_timer(self, name: str, api: TransportAPI) -> None:
        if name == "barrier":
            self._begin(api)
        elif name == "round":
            if self._open:               # safety: harvest timer not yet fired
                self._close_round(api)
            if self.round + 1 >= self.cfg.rounds:
                self._shutdown(api)
            else:
                self._start_round(api)
                api.set_timer("round", self.cfg.round_dur)
        elif name == "harvest":
            if self._open:
                self._close_round(api)
        elif name == "drain":
            api.stop()

    def on_message(self, src: str, msg, api: TransportAPI) -> None:
        kind = msg.get("kind")
        if kind == "hello":
            self._ready.add(src)
            if len(self._ready) >= len(self.client_ids):
                self._begin(api)
        elif kind == "update":
            self._on_update(src, msg, api)
        elif kind == "join":
            self.stats["rejoins"] += 1
            api.send(src, {"kind": "sync", "round": self.round,
                           "params": self._server_payload()})
        elif kind == "bye":
            self.client_logs[src] = msg.get("log", [])
            self.stats["byes"] += 1
            if self._stopping and self.stats["byes"] >= len(self.client_ids):
                api.stop()

    # -- round machinery -----------------------------------------------------

    def _start_round(self, api: TransportAPI) -> None:
        self.round += 1
        r = self.round
        if (self.eval_fn is not None and self.cfg.eval_every_rounds > 0
                and r % self.cfg.eval_every_rounds == 0):
            self._record(r)
        # fl_sim's exact per-round chain: k_q is split even when unused
        self.rkey, k_sel, self._k_q = jax.random.split(self.rkey, 3)
        idx, _ = sampler.sample_selection_indices(
            k_sel, self.cfg.n_clients, self.cfg.s_selected)
        sel = set(int(i) for i in np.asarray(idx))
        self.selection_log.append(tuple(sorted(sel)))
        self._polled = [c for c in self.client_ids if self._row[c] in sel]
        self._open = True
        self.pending = {}
        # the record carries only the round index: selection re-derives
        # from the logged key chain on replay, so it cannot diverge
        self._durable("round_start", {"kind": "round_start", "round": r})
        for c in self.client_ids:
            api.send(c, {"kind": "tick", "round": r,
                         "polled": c in self._polled})
        api.set_timer("harvest", self.cfg.harvest_frac * self.cfg.round_dur)

    def _on_update(self, src: str, msg, api: TransportAPI) -> None:
        r = int(msg.get("round"))
        seq = int(msg.get("seq", -1))
        led = self.ledger.get(src)
        if seq >= 0 and led is not None and (r, seq) <= led:
            # already durably admitted (possibly by a pre-crash
            # incarnation): exactly-once means ack-but-ignore
            self.stats["dedup"] += 1
            api.send(src, {"kind": "ack", "round": r,
                           "stale": not (self._open and r == self.round)})
            return
        # ack everything (duplicates included) so client retries stop;
        # stale=True tells the client the round already closed without it
        if not self._open or r != self.round or src not in self._polled:
            self.stats["late"] += 1
            api.send(src, {"kind": "ack", "round": r, "stale": True})
            return
        if src in self.pending:          # duplicate without a seq stamp
            api.send(src, {"kind": "ack", "round": r, "stale": False})
            return
        ent = self._admit(src, msg)
        if seq >= 0:
            self.ledger[src] = (r, seq)
        # write-ahead THEN ack: once the client sees this ack, the update
        # is durable — a restart can never lose an acknowledged admission
        self._durable("admit", {"kind": "admit", "round": r, "client": src,
                                "seq": seq, "entry": dict(ent)})
        api.send(src, {"kind": "ack", "round": r, "stale": False})
        self.pending[src] = ent
        self.stats["admitted"] += 1
        self.staleness.append(int(msg["q"]))
        if len(self.pending) == len(self._polled):
            api.cancel_timer("harvest")
            self._close_round(api)

    def _admit(self, src: str, msg) -> dict:
        """Build the pending entry. With quant_bits > 0 the update is held
        as LUQ codes + scales keyed by fold_in(k_q, row) — the
        checkpointable between-round representation."""
        bufs = [np.asarray(b, np.float32) for b in msg["params"]]
        ent = {"q": np.int32(msg["q"])}
        if self.cfg.quant_bits > 0:
            key = jax.random.fold_in(self._k_q, self._row[src])
            for b, buf in enumerate(bufs):
                enc = kops.cold_requant_rows(buf[None, :],
                                             self.cfg.quant_bits, key)
                ent[f"codes{b}"] = np.asarray(enc["codes"])
                ent[f"scale{b}"] = np.asarray(enc["scale"])
        else:
            for b, buf in enumerate(bufs):
                ent[f"raw{b}"] = buf
        return ent

    def _pending_row(self, ent: dict, b: int, dtype) -> np.ndarray:
        if self.cfg.quant_bits > 0:
            dec = kops.cold_dequant_rows(
                {"codes": ent[f"codes{b}"], "scale": ent[f"scale{b}"]},
                self.cfg.quant_bits, dtype)
            return np.asarray(dec)[0]
        return ent[f"raw{b}"]

    def _close_round(self, api: TransportAPI) -> None:
        self._open = False
        self.stats["rounds"] += 1
        admitted = sorted(self.pending, key=self._row.get)
        if len(admitted) < len(self._polled):
            self.stats["short_polls"] += 1
        # redo log, not a value log: the record names the admitted set and
        # replay re-runs the deterministic aggregation over the (already
        # logged) admit entries — closes cost O(#admitted) WAL bytes
        self._durable("close", {"kind": "close", "round": self.round,
                                "admitted": list(admitted)})
        self._apply_close(admitted)
        self._last_close = {"round": self.round, "admitted": list(admitted)}
        self.pending = {}
        if admitted:
            payload = self._server_payload()
            for c in admitted:
                api.send(c, {"kind": "reset", "round": self.round,
                             "params": payload})
                self.stats["resets"] += 1
        self._maybe_checkpoint()

    def _apply_close(self, admitted: List[str]) -> None:
        """The deterministic aggregation for one close, over entries in
        ``self.pending`` — shared verbatim by the live path and WAL
        replay, which is what makes recovered buckets bit-exact."""
        if not admitted:
            return                       # nobody delivered: w_{t+1} = w_t
        n = self.cfg.n_clients
        alpha = np.ones((n,), np.float32)
        mask = np.zeros((n,), np.float32)
        cli_f = [np.array(b) for b in self.cli_f]   # writable host copies
        for c in admitted:
            ent = self.pending[c]
            row = self._row[c]
            alpha[row] = max(float(ent["q"]), 1.0)   # eq. 3, stochastic
            mask[row] = 1.0
            for b in range(self.spec.n_buckets):
                cli_f[b][row] = self._pending_row(ent, b, cli_f[b].dtype)
        self.alpha_log.append({c: float(alpha[self._row[c]])
                               for c in admitted})
        alpha_p = round_engine.pad_client_vec(self.spec, alpha, 1.0)
        mask_p = round_engine.pad_client_vec(self.spec, mask, 0.0)
        sel_idx = jax.numpy.asarray([self._row[c] for c in admitted],
                                    jax.numpy.int32)
        self.srv_f, self.cli_f, self.ini_f = round_engine.aggregate_and_reset(
            self.spec, self.srv_f, tuple(map(jax.numpy.asarray, cli_f)),
            self.ini_f, alpha_p, mask_p, sel_idx, len(admitted), n_logical=n)

    def _shutdown(self, api: TransportAPI) -> None:
        self._record(self.cfg.rounds)
        self._stopping = True
        if self._wal is not None:
            self._wal.close()
        for c in self.client_ids:
            api.send(c, {"kind": "stop"})
        # fallback: stop even if some byes never arrive (crashed clients)
        api.set_timer("drain", 2.0 * self.cfg.round_dur)

    # -- durability: WAL, snapshots, recovery (docs/architecture.md §12) -----

    def _durable(self, point: str, rec: dict) -> None:
        """Append a WAL record, then give the chaos switch its shot. The
        kill point sits BETWEEN the durable write and every effect that
        acknowledges it (acks, resets, ticks) — exactly the interleaving
        recovery has to get right."""
        if self._wal is not None:
            self._wal.append(rec)
        if self._chaos is not None:
            self._chaos.hit(point, wal=self._wal)

    def _maybe_checkpoint(self) -> None:
        """Every ``ckpt_every`` closed rounds: rotate the WAL, snapshot
        the full state atomically, prune segments the snapshot covers."""
        if (self._wal is None or self.ckpt_every <= 0
                or self.stats["rounds"] % self.ckpt_every != 0):
            return
        seg = self._wal.rotate()         # snapshot covers everything < seg
        state = self._snapshot_state()
        state["seg"] = seg
        wal.save_snapshot(self.wal_dir, self.stats["rounds"], state)
        wal.prune_segments(self.wal_dir, seg)
        wal.prune_snapshots(self.wal_dir, keep=2)

    def _snapshot_state(self) -> dict:
        """Everything a restarted server needs BESIDES the tail of the
        WAL. Only taken at a close boundary, so ``pending`` is always
        empty here — in-flight admissions live in the log, never in the
        snapshot."""
        return {
            "server": [np.asarray(b) for b in self.srv_f],
            "clients": [np.asarray(b) for b in self.cli_f],
            "inits": [np.asarray(b) for b in self.ini_f],
            "rkey": np.asarray(self.rkey),
            "round": int(self.round),
            "ledger": dict(self.ledger),
            "epoch": int(self.epoch),
            "stats": dict(self.stats),
            "selection": list(self.selection_log),
            "alpha": list(self.alpha_log),
            "staleness": list(self.staleness),
            "curves": {k: list(v) for k, v in self.curves.items()},
            "last_close": self._last_close,
        }

    def _restore_snapshot(self, state: dict) -> int:
        self.srv_f = tuple(jax.numpy.asarray(b) for b in state["server"])
        self.cli_f = tuple(jax.numpy.asarray(b) for b in state["clients"])
        self.ini_f = tuple(jax.numpy.asarray(b) for b in state["inits"])
        self.rkey = jax.numpy.asarray(state["rkey"])
        self.round = int(state["round"])
        self.ledger = {c: tuple(v) for c, v in state["ledger"].items()}
        self.epoch = int(state["epoch"])
        self.stats.update(state["stats"])
        self.selection_log = list(state["selection"])
        self.alpha_log = list(state["alpha"])
        self.staleness = list(state["staleness"])
        self.curves = {k: list(v) for k, v in state["curves"].items()}
        self._last_close = state["last_close"]
        return int(state["seg"])

    def _recover(self) -> None:
        """Rebuild state as latest-valid-snapshot + WAL replay. Runs on a
        FRESH server object before it joins a transport; the subsequent
        ``on_start`` then executes the resume protocol instead of the
        cold-start barrier."""
        start_seg = 0
        snap = wal.latest_snapshot(self.wal_dir)
        if snap is not None:
            start_seg = self._restore_snapshot(wal.load_snapshot(snap))
        records, meta = wal.replay(self.wal_dir, start_seg)
        for rec in records:
            self._replay_record(rec)
        self.epoch += 1
        self.stats["recoveries"] += 1
        # a dead process cannot log its own death — the new incarnation
        # logs its BIRTH instead, so epoch/recovery counts survive further
        # crashes (replay of this record re-counts it)
        if self._wal is not None:
            self._wal.append({"kind": "recovered", "epoch": self.epoch})
        self._recovered = True
        self.replay_meta = dict(meta, records=len(records))

    def _replay_record(self, rec: dict) -> None:
        """Re-apply one logged transition. Appends happen strictly in
        protocol order and a tear only ever truncates the suffix, so a
        readable ``close`` always finds its admits already replayed."""
        kind = rec["kind"]
        if kind == "round_start":
            self.round = int(rec["round"])
            # same chain walk as _start_round — selection re-derives
            self.rkey, k_sel, self._k_q = jax.random.split(self.rkey, 3)
            idx, _ = sampler.sample_selection_indices(
                k_sel, self.cfg.n_clients, self.cfg.s_selected)
            sel = set(int(i) for i in np.asarray(idx))
            self.selection_log.append(tuple(sorted(sel)))
            self._polled = [c for c in self.client_ids
                            if self._row[c] in sel]
            self._open = True
            self.pending = {}
        elif kind == "admit":
            src = rec["client"]
            self.pending[src] = dict(rec["entry"])
            if rec["seq"] >= 0:
                self.ledger[src] = (int(rec["round"]), int(rec["seq"]))
            self.stats["admitted"] += 1
            self.staleness.append(int(rec["entry"]["q"]))
        elif kind == "close":
            admitted = list(rec["admitted"])
            self._open = False
            self.stats["rounds"] += 1
            if len(admitted) < len(self._polled):
                self.stats["short_polls"] += 1
            self._apply_close(admitted)
            self._last_close = {"round": self.round,
                                "admitted": admitted}
            self.pending = {}
        elif kind == "recovered":        # a prior incarnation's birth
            self.epoch = max(self.epoch, int(rec["epoch"]))
            self.stats["recoveries"] = self.epoch
        else:                            # forward-compat: ignore unknown
            pass

    def _resume(self, api: TransportAPI) -> None:
        """First ``on_start`` after recovery. Re-sends anything whose
        delivery the crash may have swallowed — clients treat resets and
        ticks idempotently by round, so over-sending is safe — and
        restarts the round cadence. Stretching the interrupted round's
        wall time is invisible to the aggregate: buckets depend on the
        selection chain, the admitted sets, and the logged entries, none
        of which see the clock."""
        self._started = True
        for c in self.client_ids:
            api.send(c, {"kind": "recover", "epoch": self.epoch,
                         "round": self.round})
        if self._last_close is not None and self._last_close["admitted"]:
            # the last close's resets may have died with the old process
            payload = self._server_payload()
            for c in self._last_close["admitted"]:
                api.send(c, {"kind": "reset",
                             "round": self._last_close["round"],
                             "params": payload})
        if self._open:
            # re-broadcast the open round's ticks and restart its clock
            for c in self.client_ids:
                api.send(c, {"kind": "tick", "round": self.round,
                             "polled": c in self._polled})
            api.set_timer("round", self.cfg.round_dur)
            if len(self.pending) == len(self._polled):
                self._close_round(api)   # everyone delivered pre-crash
            else:
                api.set_timer("harvest",
                              self.cfg.harvest_frac * self.cfg.round_dur)
        elif self.round + 1 >= self.cfg.rounds:
            self._shutdown(api)
        else:
            api.set_timer("round", 0.0)

    # -- views / checkpointing ----------------------------------------------

    def _server_payload(self) -> list:
        return [np.asarray(b) for b in self.srv_f]

    def server_params(self):
        return round_engine.unflatten_tree(self.spec, self.srv_f)

    def _record(self, r: int) -> None:
        if self.eval_fn is not None:
            self.curves["round"].append(r)
            self.curves["accuracy"].append(float(self.eval_fn(
                self.server_params())))

    def result(self) -> dict:
        return {"rounds": self.stats["rounds"],
                "final_accuracy": (self.curves["accuracy"][-1]
                                   if self.curves["accuracy"] else None),
                "curves": {k: list(v) for k, v in self.curves.items()},
                "selection": list(self.selection_log),
                "alpha": list(self.alpha_log),
                "staleness": list(self.staleness),
                "stats": dict(self.stats)}

    def checkpoint_state(self) -> dict:
        """The full restartable aggregator state as one pytree: flat
        buckets, the rng key chain, the round counter, and every pending
        admitted update (codes + scales under quant_bits > 0, raw rows
        otherwise). Feed to ``ckpt.save_engine_checkpoint`` /
        ``load_engine_checkpoint``."""
        return {
            "server": tuple(self.srv_f),
            "clients": tuple(self.cli_f),
            "inits": tuple(self.ini_f),
            "rkey": self.rkey,
            "round": np.int32(self.round),
            "pending": {c: dict(ent) for c, ent in self.pending.items()},
        }

    def save(self, directory: str, step: Optional[int] = None) -> str:
        from repro.checkpointing.ckpt import save_engine_checkpoint
        return save_engine_checkpoint(
            directory, self.stats["rounds"] if step is None else step,
            self.checkpoint_state())

    def restore_state(self, state: dict) -> None:
        self.srv_f = tuple(jax.numpy.asarray(b) for b in state["server"])
        self.cli_f = tuple(jax.numpy.asarray(b) for b in state["clients"])
        self.ini_f = tuple(jax.numpy.asarray(b) for b in state["inits"])
        self.rkey = jax.numpy.asarray(state["rkey"])
        self.round = int(state["round"])
        self.pending = {c: dict(ent)
                        for c, ent in state.get("pending", {}).items()}

    def load(self, path: str) -> None:
        from repro.checkpointing.ckpt import load_engine_checkpoint
        self.restore_state(load_engine_checkpoint(path,
                                                  self.checkpoint_state()))


def recover_server(cfg: AsyncConfig, params0, wal_dir: str, *,
                   eval_fn: Optional[Callable] = None,
                   client_ids: Optional[List[str]] = None,
                   ckpt_every: int = 0, wal_fsync: bool = True,
                   chaos=None) -> FavasAsyncServer:
    """The restart path: build a NEW server whose state is the latest
    valid snapshot plus a replay of the WAL records after it. The
    returned server's first ``on_start`` runs the resume protocol
    (``recover`` hello with the new epoch, idempotent re-sends, cadence
    restart) instead of the cold-start barrier. ``cfg`` / ``params0`` /
    ``client_ids`` must match the crashed deployment — they define the
    initial state the log is a delta against."""
    srv = FavasAsyncServer(cfg, params0, eval_fn=eval_fn,
                           client_ids=client_ids, wal_dir=wal_dir,
                           ckpt_every=ckpt_every, wal_fsync=wal_fsync,
                           chaos=chaos)
    srv._recover()
    return srv
