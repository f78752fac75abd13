"""Multi-worker serving: N event loops behind one accept path.

PR 10 made the *bytes* cheap (framed tensor bodies, ``utils/wire.py``);
what still serializes every request is the single Python event loop that
accepts, parses, and dispatches them. This module runs ``GORDO_SERVER_WORKERS``
worker event loops — each a full aiohttp server parsing requests on its
own thread — over ONE shared application state:

- **worker 0 is the primary**: its loop runs the app's startup hooks, so
  the batching engine, placement controller, SLO sampler, and streaming
  plane all live there, exactly as in single-worker mode;
- **workers 1..N-1 are parse/dispatch loops**: same routes, same
  middleware, same state dict (collection, bank, quarantine, stats, …).
  Scoring hops to the engine's loop through
  :meth:`BatchingEngine.submit` — the device work was never
  loop-parallel (it batches better when funneled), but request parse,
  JSON/tensor decode, and response serialization now run N-wide;
- **accept path**: every worker binds its own listening socket with
  ``SO_REUSEPORT`` where the platform has it (the kernel load-balances
  accepts); otherwise a tiny in-process acceptor thread owns the one
  listening socket and hands accepted connections to worker loops
  round-robin (``loop.connect_accepted_socket``).

Shared-state rule: the pool installs a ``threading.Lock`` as
``stats["lock"]`` so the middleware's counters cannot lose increments
across worker threads; with workers=1 the lock is absent and the
middleware's mutation path is byte-for-byte the old single-loop one.
Each worker's app is tagged (``app.gordo_worker``) so requests count
into ``gordo_server_worker_requests_total{worker}`` and the ``/stats``
``workers`` block — the accept-skew view.

The worker apps share the primary's state dict by construction: a
``/reload`` or rebalance swapping ``app["bank"]`` on any worker's loop
is immediately visible to every other worker (the reload lock is
cross-loop — server/utils.py:CrossLoopLock — so rebuilds still
serialize).
"""

import asyncio
import contextlib
import logging
import os
import socket
import threading
from typing import List, Optional

from aiohttp import web

logger = logging.getLogger(__name__)


def resolve_workers(workers: Optional[int] = None) -> int:
    """The worker count: explicit argument, else ``GORDO_SERVER_WORKERS``
    (default 1 — single-loop serving, the behavior-identical default)."""
    if workers is None:
        raw = os.environ.get("GORDO_SERVER_WORKERS", "1") or "1"
        try:
            workers = int(raw)
        except ValueError:
            raise ValueError(
                f"GORDO_SERVER_WORKERS must be an integer, got {raw!r}"
            ) from None
    return max(1, int(workers))


def make_worker_app(primary: web.Application, worker_id: int) -> web.Application:
    """A parse/dispatch worker app sharing the primary app's state.

    Same middleware + routes as ``build_app``; its state mapping IS the
    primary's (``_state`` is aiohttp's documented-by-usage storage dict —
    pinned by the test suite), so every handler sees one collection/bank/
    stats world and mutations propagate instantly in both directions.
    No startup hooks: background services (engine, placement, SLO,
    streaming) belong to the primary's loop only.
    """
    from gordo_components_tpu.server import CLIENT_MAX_SIZE, _stats_middleware
    from gordo_components_tpu.server.views import routes

    app = web.Application(
        client_max_size=CLIENT_MAX_SIZE, middlewares=[_stats_middleware]
    )
    app.add_routes(routes)
    # share, don't copy: a copied dict would freeze the worker's view of
    # app["bank"] at boot and a /reload would split the fleet's truth
    app._state = primary._state
    app.gordo_worker = f"w{worker_id}"
    return app


def _make_listen_socket(
    host: str, port: int, reuse_port: bool, backlog: int = 128
) -> socket.socket:
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    if reuse_port:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
    sock.bind((host, port))
    sock.listen(backlog)
    sock.setblocking(False)
    return sock


class ServerPool:
    """N worker event loops serving one shared app state (see module
    docstring). ``start()`` returns once every worker is listening;
    ``stop()`` tears the pool down in reverse order (parse workers
    first, the primary — whose cleanup stops the engine — last)."""

    def __init__(
        self,
        app: web.Application,
        host: str = "0.0.0.0",
        port: int = 5555,
        workers: Optional[int] = None,
        uds_path: Optional[str] = None,
        shm_ring: Optional[str] = None,
        reuse_port: Optional[bool] = None,
        backlog: int = 128,
    ):
        self.app = app
        self.host = host
        self.port = int(port)
        self.workers = resolve_workers(workers)
        self.uds_path = uds_path
        self.shm_ring_name = shm_ring
        self.backlog = int(backlog)
        if reuse_port is None:
            reuse_port = hasattr(socket, "SO_REUSEPORT")
        self.reuse_port = bool(reuse_port)
        self._threads: List[threading.Thread] = []
        self._loops: List[Optional[asyncio.AbstractEventLoop]] = []
        self._runners: List[Optional[web.AppRunner]] = []
        self._sockets: List[socket.socket] = []
        self._acceptor: Optional[threading.Thread] = None
        self._accept_sock: Optional[socket.socket] = None
        self._shm_server = None
        self._stop_evt = threading.Event()
        self._started = False

    # ------------------------------------------------------------------ #

    def start(self, timeout: float = 60.0) -> None:
        if self._started:
            raise RuntimeError("pool already started")
        self._started = True
        stats = self.app["stats"]
        if self.workers > 1 and stats.get("lock") is None:
            # the middleware's counters now mutate from N loop threads;
            # the lock restores the lost-increment-free contract
            stats["lock"] = threading.Lock()
        if self.workers > 1 and self.app.get("bank_enabled"):
            # one device-dispatch lock shared by every engine (the
            # primary's and each worker's): parse + coalesce run N-wide,
            # bank calls serialize where the device would anyway
            self.app["bank_dispatch_lock"] = threading.Lock()
            # worker engines register here so a bank swap (/reload,
            # rebalance, adaptation) can repoint ALL of them
            self.app["worker_engines"] = []
        transports = dict(self.app.get("transports") or {})
        if self.uds_path:
            # advertised through /models so a co-located client's
            # transport="auto" can find (and stat-check) the socket
            transports["uds"] = self.uds_path
        if self.shm_ring_name:
            transports["shm"] = self.shm_ring_name
        if transports:
            self.app["transports"] = transports
        # one socket per worker under SO_REUSEPORT (kernel balances the
        # accepts); one shared socket + acceptor thread otherwise
        per_worker_sockets: List[Optional[socket.socket]] = []
        if self.reuse_port:
            first = _make_listen_socket(
                self.host, self.port, True, self.backlog
            )
            self.port = first.getsockname()[1]  # resolve port=0 once
            per_worker_sockets.append(first)
            for _ in range(1, self.workers):
                per_worker_sockets.append(
                    _make_listen_socket(self.host, self.port, True, self.backlog)
                )
        else:
            self._accept_sock = _make_listen_socket(
                self.host, self.port, False, self.backlog
            )
            self._accept_sock.setblocking(True)
            self.port = self._accept_sock.getsockname()[1]
            per_worker_sockets = [None] * self.workers
        self._sockets = [s for s in per_worker_sockets if s is not None]

        apps = [self.app] + [
            make_worker_app(self.app, i) for i in range(1, self.workers)
        ]
        if self.workers > 1:
            # the primary parses too: tag it so the skew view is complete
            self.app.gordo_worker = "w0"
        self._loops = [None] * self.workers
        self._runners = [None] * self.workers
        ready = [threading.Event() for _ in range(self.workers)]
        errors: List[Optional[BaseException]] = [None] * self.workers
        for i in range(self.workers):
            t = threading.Thread(
                target=self._worker_main,
                args=(i, apps[i], per_worker_sockets[i], ready[i], errors),
                name=f"gordo-worker-{i}",
                daemon=True,
            )
            self._threads.append(t)
            t.start()
        for i, evt in enumerate(ready):
            if not evt.wait(timeout):
                self.stop()
                raise RuntimeError(f"worker {i} did not become ready")
            if errors[i] is not None:
                self.stop()
                raise RuntimeError(f"worker {i} failed to start") from errors[i]
        if self._accept_sock is not None:
            self._acceptor = threading.Thread(
                target=self._accept_loop, name="gordo-acceptor", daemon=True
            )
            self._acceptor.start()
        if self.shm_ring_name:
            from gordo_components_tpu.server.transport import ShmServer

            self._shm_server = ShmServer.create(self.app, self.shm_ring_name)
        logger.info(
            "serving pool up: %d worker(s) on %s:%d%s%s (reuse_port=%s)",
            self.workers, self.host, self.port,
            f" + uds {self.uds_path}" if self.uds_path else "",
            f" + shm {self.shm_ring_name}" if self.shm_ring_name else "",
            self.reuse_port,
        )

    def _worker_main(self, idx, app, sock, ready_evt, errors) -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loops[idx] = loop
        runner = web.AppRunner(app, handle_signals=False)
        worker_engine = None
        try:
            loop.run_until_complete(runner.setup())
            self._runners[idx] = runner
            if idx > 0:
                worker_engine = self._start_worker_engine(app, loop, idx)
            if sock is not None:
                loop.run_until_complete(web.SockSite(runner, sock).start())
            if idx == 0 and self.uds_path:
                # ONE unix acceptor is plenty: UDS accept is not the
                # bottleneck its TCP sibling is, and the parse work a
                # UDS request brings still lands on whichever loop the
                # kernel wakes — here, the primary's
                loop.run_until_complete(
                    web.UnixSite(runner, self.uds_path).start()
                )
        except BaseException as exc:  # startup failed: report, don't hang
            errors[idx] = exc
            ready_evt.set()
            with contextlib.suppress(Exception):
                loop.run_until_complete(runner.cleanup())
            loop.close()
            return
        ready_evt.set()
        try:
            loop.run_forever()
        finally:
            if worker_engine is not None:
                with contextlib.suppress(Exception):
                    loop.run_until_complete(worker_engine.stop())
            with contextlib.suppress(Exception):
                loop.run_until_complete(runner.cleanup())
            with contextlib.suppress(Exception):
                loop.run_until_complete(loop.shutdown_asyncgens())
            loop.close()

    def _start_worker_engine(self, app, loop, idx):
        """A local batching engine for this worker's loop, over the one
        shared bank: requests parsed here never pay a cross-loop hop,
        coalescing stays loop-local, and the shared dispatch lock
        serializes the bank calls the device would serialize anyway.
        Uninstrumented (registry=False): the primary engine keeps the
        ``gordo_engine_*`` metric surface; per-worker counters surface
        through /stats ``worker_engines``."""
        from gordo_components_tpu.server.bank import BatchingEngine

        bank = self.app.get("bank")
        lock = self.app.get("bank_dispatch_lock")
        if bank is None or lock is None or not len(bank):
            return None
        cfg = self.app.get("bank_config") or {}
        engine = BatchingEngine(
            bank,
            max_batch=cfg.get("max_batch", 64),
            flush_ms=cfg.get("flush_ms", 0.0),
            max_queue=cfg.get("max_queue"),
            registry=False,
            dispatch_lock=lock,
        )
        loop.call_soon(engine.start)
        app.gordo_engine = engine
        self.app["worker_engines"].append((f"w{idx}", engine))
        return engine

    def _accept_loop(self) -> None:
        """SO_REUSEPORT-less fallback: one blocking acceptor handing
        connections to worker loops round-robin. The hand-off is a
        thread-safe hop onto the target loop, which adopts the connected
        socket into its own aiohttp protocol stack."""
        assert self._accept_sock is not None
        idx = 0
        while not self._stop_evt.is_set():
            try:
                conn, _peer = self._accept_sock.accept()
            except OSError:
                break  # socket closed by stop()
            loop = self._loops[idx % self.workers]
            runner = self._runners[idx % self.workers]
            idx += 1
            if loop is None or runner is None or not loop.is_running():
                conn.close()
                continue
            conn.setblocking(False)

            async def _adopt_coro(conn=conn, runner=runner):
                # runner.server is the aiohttp protocol factory for this
                # worker's app
                await asyncio.get_running_loop().connect_accepted_socket(
                    runner.server, conn
                )

            asyncio.run_coroutine_threadsafe(_adopt_coro(), loop)

    # ------------------------------------------------------------------ #

    def stop(self, timeout: float = 30.0) -> None:
        self._stop_evt.set()
        if self._shm_server is not None:
            self._shm_server.close()
            self._shm_server = None
        if self._accept_sock is not None:
            with contextlib.suppress(OSError):
                self._accept_sock.close()
        if self._acceptor is not None:
            self._acceptor.join(timeout)
        # parse workers first; the primary last — its cleanup stops the
        # engine, and in-flight worker requests may still be awaiting it
        for i in range(self.workers - 1, -1, -1):
            loop = self._loops[i] if i < len(self._loops) else None
            if loop is not None and loop.is_running():
                loop.call_soon_threadsafe(loop.stop)
            if i < len(self._threads):
                self._threads[i].join(timeout)
        for sock in self._sockets:
            with contextlib.suppress(OSError):
                sock.close()
        if self.uds_path and os.path.exists(self.uds_path):
            with contextlib.suppress(OSError):
                os.unlink(self.uds_path)

    def wait(self) -> None:
        """Block the calling (main) thread until interrupted — the
        ``run_server`` CLI's foreground behavior."""
        try:
            while any(t.is_alive() for t in self._threads):
                for t in self._threads:
                    t.join(1.0)
        except KeyboardInterrupt:
            pass


__all__ = ["ServerPool", "make_worker_app", "resolve_workers"]
