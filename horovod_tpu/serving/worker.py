"""Serving worker: one model replica dialing the frontend.

A worker owns a :class:`~.engine.ServingEngine` and a single control-plane
connection to the frontend (``serving/server.py``). The protocol from the
worker's side:

1. connect, ``MSG_SERVE_HELLO(role=worker, name, capacity=max_batch)``;
2. ``MSG_SERVE_SUBMIT`` frames feed :meth:`ServingEngine.submit`; each
   request's completion callback ships ``MSG_SERVE_RESULT`` back;
3. ``MSG_SERVE_CANCEL`` frames evict the request between engine steps
   (KV blocks back in the pool within one scheduler sweep); a
   ``MSG_SERVE_DRAIN`` quiesces the replica — queued work is handed back
   as retryable ``SERVE_REJECTED`` (the frontend re-dispatches it), new
   submits are refused, in-flight generations run to completion;
4. heartbeats (``MSG_HEARTBEAT``) every ``HOROVOD_HEARTBEAT_INTERVAL`` and
   ``MSG_METRICS`` registry snapshots every ``HOROVOD_METRICS_INTERVAL``
   keep the frontend's liveness and pod ``/metrics`` views current.

Recovery mirrors the PR-4 worker-side control plane: a dropped connection
triggers reconnect with deterministic per-replica jittered backoff
(``HOROVOD_RECONNECT_JITTER`` — a mass reconnect after a frontend death
must not land as one synchronized herd on the promoted standby); in-flight
generations keep running through the outage, their results park in an
unsent list and replay after reconnect (the frontend dedupes by request
id, so replaying a result the frontend already re-admitted elsewhere is
harmless). When redials keep failing the worker probes the rendezvous KV
for ``serve.addr.{gen}.f{n}`` — a promoted standby frontend — re-aims at
it, and seeds its :class:`~..runtime.wire.FenceGuard` from
``serve.lease.{gen}`` so the deposed frontend's frames are rejected from
the first exchange with the new leader.

``python -m horovod_tpu.serving.worker --addr HOST:PORT`` is the replica
entry point the CI pod-smoke and the chaos drills spawn; every replica
builds the identical deterministic tiny model from a fixed PRNG seed,
standing in for "every replica restored the same checkpoint".
"""

from __future__ import annotations

import argparse
import logging
import os
import socket
import threading
import time
from typing import Dict, List, Optional, Tuple

from .. import blackbox as _blackbox
from ..metrics import local_snapshot
from ..runtime import wire
from ..runtime.coordinator import (MSG_HEARTBEAT, MSG_METRICS,
                                   _backoff_schedule, _resolve_key)
from ..utils import compile_cache
from .engine import ServingConfig, ServingEngine
from .scheduler import CANCELLED, DONE, QueueFull, Request

logger = logging.getLogger("horovod_tpu")


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    return float(raw) if raw else default


class ServingWorker:
    """Runs one engine replica against a frontend address."""

    def __init__(self, host: str, port: int, engine: ServingEngine,
                 name: str = "worker-0", rank: int = 0,
                 secret: Optional[str] = None, gen: int = 0):
        self.host = host
        self.port = int(port)
        self.engine = engine
        self.name = name
        self.rank = int(rank)
        self.gen = int(gen)
        self.secret = (secret if secret is not None
                       else os.environ.get("HVD_SECRET", ""))
        self._stop = threading.Event()
        self._sock: Optional[socket.socket] = None
        self._send_lock = threading.Lock()
        self._seq = 0
        # request id -> encoded RESULT payload not yet delivered (either
        # the connection was down at completion, or the send failed)
        self._unsent: Dict[str, bytes] = {}
        self._unsent_lock = threading.Lock()
        self._seen: Dict[str, bool] = {}  # dedupe of in-flight resubmits
        self._guard = wire.FenceGuard(rank=self.rank)
        self._fo = 0          # failover addresses consumed so far
        self.draining = False
        self._last_saturation = 0.0

    # -------------------------------------------------------------- wire
    def _send(self, msg_type: int, payload: bytes) -> bool:
        sock = self._sock
        if sock is None:
            return False
        try:
            with self._send_lock:
                self._seq += 1
                wire.send_frame(sock, self.secret, msg_type, self._seq,
                                self.rank, payload,
                                fence=self._guard.epoch)
            return True
        except OSError:
            return False

    def _probe_failover(self) -> None:
        """The dead frontend may have left a promoted standby behind: look
        for the next serving failover address with a short timeout and,
        when published, re-aim every further dial at it — learning the new
        fencing epoch first, so the deposed frontend's frames are rejected
        from here on."""
        try:
            addr, secret = _resolve_key(
                f"serve.addr.{self.gen}.f{self._fo + 1}", timeout=0.3)
        except Exception:
            return  # nothing promoted (yet); keep redialing the old address
        self._fo += 1
        from ..runtime import lease as _lease

        if _lease.lease_enabled():
            self._guard.observe(_lease.read_lease_epoch(
                self.gen, key=f"serve.lease.{self.gen}"))
        host, port = addr.rsplit(":", 1)
        self.host, self.port = host, int(port)
        if secret:
            self.secret = secret
        logger.warning("worker %s: following serving frontend failover "
                       "#%d to %s (fence epoch %d)", self.name, self._fo,
                       addr, self._guard.epoch)

    def _connect(self) -> socket.socket:
        """Dial + HELLO with capped, per-replica-jittered exponential
        backoff, forever (the frontend may be restarting — serving workers
        outlive it). Failed attempts probe the KV for a promoted standby."""
        jitter = _env_float("HOROVOD_RECONNECT_JITTER", 0.0)
        attempt = 0
        while not self._stop.is_set():
            attempt += 1
            try:
                sock = socket.create_connection((self.host, self.port),
                                                timeout=5.0)
                sock.settimeout(1.0)
                wire.send_frame(
                    sock, self.secret, wire.MSG_SERVE_HELLO, 0, self.rank,
                    wire.encode_serve_hello(wire.SERVE_ROLE_WORKER,
                                            self.name,
                                            self.engine.config.max_batch),
                    fence=self._guard.epoch)
                # a drain is scoped to the frontend session that issued
                # it; this HELLO opened a new session (possibly with a
                # promoted standby that knows nothing of the drain), so
                # the replica serves again
                self.draining = False
                return sock
            except OSError as exc:
                if attempt >= 2:
                    self._probe_failover()
                delay = _backoff_schedule(self.rank, attempt, 0.1, 5.0,
                                          jitter)
                logger.info("worker %s: frontend unreachable (%s); "
                            "retrying in %.2fs", self.name, exc, delay)
                if self._stop.wait(delay):
                    break
        raise wire.ShutdownError("serving worker stopped")

    # ---------------------------------------------------------- requests
    def _on_submit(self, payload: bytes) -> None:
        (rid, prompt, max_new, eos, deadline,
         _priority) = wire.decode_serve_submit_ex(payload)
        if self.draining:
            # quiesced: hand the request straight back for re-dispatch
            self._queue_result(rid, wire.encode_serve_result(
                rid, wire.SERVE_REJECTED, [], "worker draining"))
            return
        with self._unsent_lock:
            if rid in self._seen:
                # duplicate dispatch (frontend resend race): the original
                # submission's callback / unsent replay will answer
                return
            self._seen[rid] = True
            if len(self._seen) > 8192:
                for k in list(self._seen)[:4096]:
                    del self._seen[k]
        try:
            self.engine.submit(prompt, max_new, request_id=rid,
                               eos_id=eos, callback=self._on_done,
                               deadline=deadline or None)
        except QueueFull:
            self._record_saturation()
            with self._unsent_lock:
                # handing the request back: forget the id, or the
                # frontend's re-dispatch of this retryable rejection
                # would be swallowed as a duplicate (mirrors _on_drain)
                self._seen.pop(rid, None)
            self._queue_result(rid, wire.encode_serve_result(
                rid, wire.SERVE_REJECTED, [],
                "replica queue full"))
        except ValueError as exc:
            self._queue_result(rid, wire.encode_serve_result(
                rid, wire.SERVE_FAILED, [], str(exc)))

    def _record_saturation(self) -> None:
        """Rate-limited blackbox breadcrumb naming WHICH resource is the
        bottleneck — the doctor's serving_overload evidence."""
        now = time.monotonic()
        if now - self._last_saturation < 1.0:
            return
        self._last_saturation = now
        _blackbox.record(
            _blackbox.K_ANOMALY, "serving_saturation",
            "replica %s saturated resource=%s"
            % (self.name, self.engine.saturated_resource()),
            rank=self.rank)

    def _on_cancel(self, payload: bytes) -> None:
        rid, reason = wire.decode_serve_cancel(payload)
        # evicted between engine steps; KV blocks return to the pool
        # within one scheduler sweep
        self.engine.cancel(rid, reason or "cancelled by frontend")
        with self._unsent_lock:
            # a parked result for a cancelled request would replay as
            # noise the frontend already tombstoned — drop it
            self._unsent.pop(rid, None)

    def _on_drain(self, payload: bytes) -> None:
        reason = wire.decode_serve_drain(payload)
        self.draining = True
        evicted = self.engine.scheduler.evict_queued()
        logger.warning(
            "worker %s: draining (%s) — %d queued request(s) handed back, "
            "%d in-flight running to completion", self.name, reason,
            len(evicted), self.engine.scheduler.active_count())
        with self._unsent_lock:
            for req in evicted:
                # forget the id so a post-drain restart of this replica
                # can accept a re-dispatch of the same request
                self._seen.pop(req.id, None)
        for req in evicted:
            self._queue_result(req.id, wire.encode_serve_result(
                req.id, wire.SERVE_REJECTED, [],
                "worker draining: requeue"))

    def _on_done(self, req: Request) -> None:
        if req.state == DONE:
            payload = wire.encode_serve_result(
                req.id, wire.SERVE_OK, req.output, "",
                req.latency() or 0.0)
        elif req.state == CANCELLED:
            payload = wire.encode_serve_result(
                req.id, wire.SERVE_CANCELLED, [], req.error)
        else:
            payload = wire.encode_serve_result(
                req.id, wire.SERVE_FAILED, [], req.error)
        self._queue_result(req.id, payload)

    def _queue_result(self, rid: str, payload: bytes) -> None:
        with self._unsent_lock:
            self._unsent[rid] = payload
        self._flush_results()

    def _flush_results(self) -> None:
        with self._unsent_lock:
            items: List[Tuple[str, bytes]] = list(self._unsent.items())
        for rid, payload in items:
            if not self._send(wire.MSG_SERVE_RESULT, payload):
                return  # connection down; replay after reconnect
            with self._unsent_lock:
                self._unsent.pop(rid, None)
                # answered as far as this replica can tell: a send into a
                # frontend that has just died succeeds and is lost, and the
                # promoted standby's re-dispatch of the id must then run
                # again, not be swallowed as a duplicate
                self._seen.pop(rid, None)

    # ---------------------------------------------------------- heartbeat
    def _heartbeat_loop(self) -> None:
        hb = _env_float("HOROVOD_HEARTBEAT_INTERVAL", 5.0)
        metrics_every = _env_float("HOROVOD_METRICS_INTERVAL", 10.0)
        last_metrics = 0.0
        while not self._stop.wait(min(hb, 1.0)):
            self._send(MSG_HEARTBEAT, b"")
            now = time.monotonic()
            if now - last_metrics >= metrics_every:
                last_metrics = now
                self._send(MSG_METRICS, wire.encode_metrics_report(
                    self.rank, time.time(), local_snapshot()))

    # ----------------------------------------------------------- run loop
    def run(self) -> None:
        """Serve until :meth:`stop`: engine loop + heartbeats in the
        background, this thread reading frontend frames (reconnecting on
        every connection failure — including fence rejections of a
        deposed frontend's traffic, which surface as FrameErrors and land
        back here to redial the promoted one)."""
        self.engine.start()
        _blackbox.maybe_activate()
        hb = threading.Thread(target=self._heartbeat_loop,
                              name="hvd-serve-worker-hb", daemon=True)
        hb.start()
        try:
            while not self._stop.is_set():
                try:
                    self._sock = self._connect()
                except wire.ShutdownError:
                    return
                logger.info("worker %s connected to frontend", self.name)
                self._flush_results()  # replay results from the outage
                try:
                    while not self._stop.is_set():
                        frame = wire.recv_frame(self._sock, self.secret,
                                                self._stop,
                                                guard=self._guard)
                        if frame.msg_type == wire.MSG_SERVE_SUBMIT:
                            self._on_submit(frame.payload)
                        elif frame.msg_type == wire.MSG_SERVE_CANCEL:
                            self._on_cancel(frame.payload)
                        elif frame.msg_type == wire.MSG_SERVE_DRAIN:
                            self._on_drain(frame.payload)
                except wire.ShutdownError:
                    return
                except (ConnectionError, OSError) as exc:
                    if self._stop.is_set():
                        return
                    logger.warning("worker %s: frontend connection lost "
                                   "(%s); reconnecting", self.name, exc)
                    self._sock = None
        finally:
            self.engine.stop()
            hb.join(timeout=2)

    def start(self) -> "ServingWorker":
        threading.Thread(target=self.run, name="hvd-serve-worker",
                         daemon=True).start()
        return self

    def stop(self) -> None:
        self._stop.set()
        sock, self._sock = self._sock, None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass


def build_replica_engine(vocab_size: int = 251, num_layers: int = 2,
                         num_heads: int = 2, d_model: int = 64,
                         max_seq_len: int = 128,
                         config: Optional[ServingConfig] = None,
                         seed: int = 0) -> ServingEngine:
    """Deterministic tiny-replica engine: every process that calls this
    with the same arguments holds bit-identical parameters (fixed PRNG
    seed), standing in for 'restored the same checkpoint' in tests,
    benchmarks and the CI pod smoke."""
    import jax
    import jax.numpy as jnp

    from ..models.transformer import TransformerLM

    model = TransformerLM(vocab_size=vocab_size, num_layers=num_layers,
                          num_heads=num_heads, d_model=d_model,
                          max_seq_len=max_seq_len)
    # one compiled program, not an op-by-op forward: the init's dummy
    # forward is dead code under jit, and the persistent cache keeps it
    params = jax.jit(lambda key: model.init(
        key, jnp.zeros((1, 8), jnp.int32))["params"])(
        jax.random.PRNGKey(seed))
    cfg = config or ServingConfig(max_context=max_seq_len)
    if cfg.max_context is None or cfg.max_context > max_seq_len:
        cfg.max_context = max_seq_len
    return ServingEngine(model, params, cfg)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="horovod_tpu serving worker replica")
    ap.add_argument("--addr", required=True, help="frontend HOST:PORT")
    ap.add_argument("--name", default=None)
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--gen", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=251)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--blocks", type=int, default=None)
    ap.add_argument("--block-size", type=int, default=None)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--slow", type=float, default=0.0,
                    help="stall every engine step by SLOW seconds "
                         "(slow-replica chaos drill)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    host, port = args.addr.rsplit(":", 1)
    cfg = ServingConfig(block_size=args.block_size, num_blocks=args.blocks,
                        max_batch=args.max_batch, max_context=args.max_seq)
    engine = build_replica_engine(
        vocab_size=args.vocab, num_layers=args.layers, num_heads=args.heads,
        d_model=args.d_model, max_seq_len=args.max_seq, config=cfg)
    if args.slow > 0:
        engine.step_delay = args.slow
    name = args.name or f"worker-{args.rank}"
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s " + name + " %(message)s")
    _blackbox.maybe_activate()
    worker = ServingWorker(host, int(port), engine, name=name,
                           rank=args.rank, gen=args.gen)
    try:
        worker.run()
    except KeyboardInterrupt:
        worker.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
