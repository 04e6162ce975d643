"""paddle.distributed.rpc — remote procedure calls between worker processes.

Reference analog: python/paddle/distributed/rpc/rpc.py (init_rpc / rpc_sync /
rpc_async / shutdown over the brpc RpcAgent,
fluid/distributed/rpc/rpc_agent.cc): workers register by name through a
bootstrap store, then ship pickled Python callables to each other and wait on
futures.

TPU-native shape: transport is the native actor message bus
(core/native/message_bus.cpp — same TCP frames the fleet executor uses)
instead of brpc; the bootstrap store is the native TCPStore. Each worker runs
a server thread that executes incoming calls on a small thread pool, so a
worker can serve requests while it issues its own.

SECURITY: payloads are pickled callables — executing them is the point of
RPC, which means anyone who can connect to the bus port can run code, the
same trust model as the reference's brpc agent. Deploy only on a trusted
cluster network. Mitigations: set PADDLE_BIND_IP to keep the listener off
public interfaces, and PADDLE_BUS_TOKEN (the launcher sets one per job) so
unauthenticated connections are dropped before a single frame is unpickled.
"""
from __future__ import annotations

import os
import pickle
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List, NamedTuple, Optional

from ..tcp_store import TCPStore
from ..fleet_executor.bus import MessageBus

__all__ = ["init_rpc", "rpc_sync", "rpc_async", "shutdown", "get_worker_info",
           "get_all_worker_infos", "WorkerInfo"]


class WorkerInfo(NamedTuple):
    name: str
    rank: int
    ip: str
    port: int


# message types on the bus (payloads are pickled tuples)
_REQ = 10       # (call_id, fn, args, kwargs)
_RESP = 11      # (call_id, ok, value)
_BYE = 12

# actor id layout: rank r listens at actor id (r+1); plain, collision-free
_ACTOR = lambda rank: rank + 1


class _Agent:
    def __init__(self, name: str, rank: int, world_size: int,
                 store: TCPStore, bus: MessageBus,
                 workers: List[WorkerInfo]):
        self.name = name
        self.rank = rank
        self.world_size = world_size
        self.store = store
        self.bus = bus
        self.workers = workers
        self.by_name = {w.name: w for w in workers}
        self._calls: Dict[int, Future] = {}
        self._next_call = [0]
        self._mu = threading.Lock()
        self._stop = threading.Event()
        self._pool = ThreadPoolExecutor(max_workers=4,
                                        thread_name_prefix=f"rpc-{name}")
        self._serve_thread = threading.Thread(target=self._serve, daemon=True,
                                              name=f"rpc-serve-{name}")
        self._serve_thread.start()

    # ------------------------------------------------------------- serving

    def _serve(self):
        me = _ACTOR(self.rank)
        while not self._stop.is_set():
            msg = self.bus.recv(me, timeout_ms=200)
            if msg is None:
                continue
            src, typ, payload = msg
            if typ == _BYE:
                break
            if typ == _REQ:
                call_id, fn, args, kwargs = pickle.loads(payload)
                self._pool.submit(self._execute, src, call_id, fn, args,
                                  kwargs)
            elif typ == _RESP:
                call_id, ok, value = pickle.loads(payload)
                with self._mu:
                    fut = self._calls.pop(call_id, None)
                if fut is not None:
                    if ok:
                        fut.set_result(value)
                    else:
                        fut.set_exception(value)

    def _execute(self, src_actor: int, call_id: int, fn, args, kwargs):
        try:
            result = (call_id, True, fn(*args, **kwargs))
        except BaseException as e:  # ship the exception back (reference does)
            result = (call_id, False, e)
        # pickle OUTSIDE the send guard: an unpicklable result/exception must
        # still produce a response or the caller's future never completes
        try:
            blob = pickle.dumps(result)
        except Exception as pe:
            blob = pickle.dumps((call_id, False, RuntimeError(
                f"rpc result not picklable: {pe}")))
        try:
            self.bus.send(_ACTOR(self.rank), src_actor, _RESP, blob)
        except Exception:
            pass  # caller gone

    # ------------------------------------------------------------- calling

    def call(self, to: str, fn, args, kwargs, timeout: Optional[float]
             ) -> Future:
        dst = self.by_name[to]
        with self._mu:
            call_id = self._next_call[0]
            self._next_call[0] += 1
            fut: Future = Future()
            self._calls[call_id] = fut
        self.bus.send(_ACTOR(self.rank), _ACTOR(dst.rank), _REQ,
                      pickle.dumps((call_id, fn, args, kwargs)))
        return fut  # deadline enforcement is Future.result(timeout)

    def shutdown(self):
        self._stop.set()
        try:
            self.bus.send(_ACTOR(self.rank), _ACTOR(self.rank), _BYE)
        except Exception:
            pass
        self._serve_thread.join(timeout=5)
        self._pool.shutdown(wait=True)
        self.bus.close()
        if self.rank != 0:
            self.store.close() if hasattr(self.store, "close") else None


_AGENT: Optional[_Agent] = None


def init_rpc(name: str, rank: Optional[int] = None,
             world_size: Optional[int] = None,
             master_endpoint: Optional[str] = None):
    """Join the RPC world (reference rpc.init_rpc). master_endpoint
    "host:port" hosts the bootstrap TCPStore on rank 0; PADDLE_MASTER and
    PADDLE_TRAINER_ID/PADDLE_TRAINERS_NUM fill unset args (env contract)."""
    global _AGENT
    if _AGENT is not None:
        raise RuntimeError("rpc already initialized")
    rank = int(os.environ.get("PADDLE_TRAINER_ID", -1)) if rank is None else rank
    world_size = int(os.environ.get("PADDLE_TRAINERS_NUM", -1)) \
        if world_size is None else world_size
    master_endpoint = master_endpoint or os.environ.get("PADDLE_MASTER")
    if rank < 0 or world_size <= 0 or not master_endpoint:
        raise ValueError("init_rpc needs rank, world_size and master_endpoint")
    host, port = master_endpoint.rsplit(":", 1)
    store = TCPStore(host, int(port), is_master=(rank == 0),
                     world_size=world_size)

    bus = MessageBus(rank)
    my_port = bus.listen(0)
    my_ip = "127.0.0.1" if host in ("127.0.0.1", "localhost") else \
        os.environ.get("POD_IP", "127.0.0.1")
    store.set(f"rpc/worker/{rank}",
              pickle.dumps(WorkerInfo(name, rank, my_ip, my_port)))
    workers: List[WorkerInfo] = []
    for r in range(world_size):
        store.wait([f"rpc/worker/{r}"], timeout=300)
        workers.append(pickle.loads(store.get(f"rpc/worker/{r}")))
    for w in workers:
        bus.route(_ACTOR(w.rank), w.rank)
        if w.rank == rank:
            bus.open_mailbox(_ACTOR(w.rank))
        else:
            bus.connect(w.rank, w.ip, w.port)
    agent = _Agent(name, rank, world_size, store, bus, workers)
    # barrier: everyone connected before anyone issues calls. The agent is
    # published BEFORE it: a peer that leaves the barrier first may call in
    # while this process still sleeps in it, and what it calls
    # (get_worker_info, a nested rpc) needs the global. A timed-out init
    # takes it back and tears the agent down, so a retry isn't blocked by a
    # half-initialized world.
    _AGENT = agent
    store.add("rpc/ready", 1)
    deadline = time.time() + 300
    while int(store.add("rpc/ready", 0)) < world_size:
        if time.time() > deadline:
            _AGENT = None
            agent.shutdown()
            raise TimeoutError("rpc init barrier timed out")
        time.sleep(0.02)


def rpc_sync(to: str, fn, args=None, kwargs=None, timeout: float = -1):
    """Execute fn on worker `to`, blocking for the result (reference
    rpc_sync; fn/args travel pickled)."""
    fut = rpc_async(to, fn, args=args, kwargs=kwargs, timeout=timeout)
    return fut.result(timeout if timeout and timeout > 0 else None)


def rpc_async(to: str, fn, args=None, kwargs=None, timeout: float = -1):
    if _AGENT is None:
        raise RuntimeError("call init_rpc first")
    return _AGENT.call(to, fn, tuple(args or ()), dict(kwargs or {}), timeout)


def get_worker_info(name: Optional[str] = None) -> WorkerInfo:
    if _AGENT is None:
        raise RuntimeError("call init_rpc first")
    if name is None:
        return _AGENT.by_name[_AGENT.name]
    return _AGENT.by_name[name]


def get_all_worker_infos() -> List[WorkerInfo]:
    if _AGENT is None:
        raise RuntimeError("call init_rpc first")
    return list(_AGENT.workers)


def shutdown():
    """Graceful: a store barrier drains in-flight work before agents die
    (reference shutdown synchronizes through the master). The master keeps
    its store alive until every other rank marks itself exited — otherwise a
    rank still polling the barrier would hit a dead socket."""
    global _AGENT
    if _AGENT is None:
        return
    agent = _AGENT
    store = agent.store
    store.add("rpc/done", 1)
    deadline = time.time() + 300
    while int(store.add("rpc/done", 0)) < agent.world_size:
        if time.time() > deadline:
            break
        time.sleep(0.02)
    if agent.rank != 0:
        store.set(f"rpc/exited/{agent.rank}", b"1")
    else:
        for r in range(1, agent.world_size):
            try:
                store.wait([f"rpc/exited/{r}"], timeout=60)
            except Exception:
                break  # a peer died; close anyway
    _AGENT = None
    agent.shutdown()
