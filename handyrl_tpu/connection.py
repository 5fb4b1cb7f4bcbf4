"""Control-plane messaging: framed pickle over sockets and pipes.

This is the learner<->actor transport (role parity with
/root/reference/handyrl/connection.py:14-224).  It is deliberately NOT
the data plane: device-to-device traffic (gradient reduction, sharded
batches) rides XLA collectives over ICI inside jitted programs (see
handyrl_tpu.parallel); this module only moves control messages and
compressed trajectories between CPU processes/machines.

Wire format: 4-byte big-endian length + pickle payload.  Large payloads
are sent in chunks so a slow peer cannot wedge the sender's buffer.
"""

import io
import multiprocessing as mp
# ``mp.connection`` is a lazily-bound submodule: it only exists after
# something imports it (locally that was a Pipe construction).  A
# remote-mode learner with device replay never builds a pipe, so the
# recv loop's first ``mp.connection.wait`` would die with
# AttributeError on the first worker connection — import it EXPLICITLY
# (found live by the StallWatchdog: "recv_loop silent ... <thread
# gone>" on a --train-server drive)
import multiprocessing.connection  # noqa: F401
import pickle
import queue
import random
import socket
import struct
import threading
import time
from typing import Any, Callable, Dict, Iterable, Optional

from .telemetry import unwrap_trace, wrap_trace

CHUNK = 1 << 14  # 16 KiB send granularity

# Ceiling on a single control-plane frame.  Legitimate frames top out
# at a pickled model snapshot (MBs); a corrupt or adversarial 4-byte
# header could otherwise demand a ~4 GiB allocation before the first
# payload byte arrives.  Configurable per connection via the
# `max_frame_bytes` config key.
DEFAULT_MAX_FRAME_BYTES = 1 << 30  # 1 GiB


class FrameError(ConnectionError):
    """Corrupt, truncated, or oversized control-plane frame.

    Subclasses ``ConnectionError`` deliberately: every dead-peer
    handler (``_PEER_GONE``, ``QueueCommunicator`` drop paths) already
    treats the peer as gone, which is the right response to a peer
    whose byte stream can no longer be trusted."""


def send_recv(conn, sdata):
    """One request/reply round trip."""
    conn.send(sdata)
    # every caller's peer is supervised or heartbeat-swept, so a wedged
    # reply ends in eviction (learner sweep) or child respawn, never a
    # silent forever-block
    # jaxlint: disable=unbounded-recv -- wedge bounded by peer supervision / heartbeat sweep
    return conn.recv()


class FramedConnection:
    """Length-prefixed pickle messaging over a stream socket.

    Same duck-type as ``mp.Pipe`` connections (``send``/``recv``/
    ``close``/``fileno``) so every layer above can hold either.
    """

    def __init__(self, sock: socket.socket,
                 max_frame_bytes: int = DEFAULT_MAX_FRAME_BYTES):
        self.sock = sock
        self.max_frame_bytes = int(max_frame_bytes
                                   or DEFAULT_MAX_FRAME_BYTES)

    def fileno(self):
        return self.sock.fileno()

    def close(self):
        if self.sock is not None:
            self.sock.close()
            self.sock = None

    def send(self, data: Any):
        payload = pickle.dumps(data, protocol=pickle.HIGHEST_PROTOCOL)
        header = struct.pack("!I", len(payload))
        buf = memoryview(header + payload)
        while buf:
            sock = self.sock
            if sock is None:
                # closed under us (kill/teardown race): a typed
                # dead-peer error, not an AttributeError on None
                raise ConnectionResetError("connection closed")
            n = sock.send(buf[:CHUNK])
            buf = buf[n:]

    def _recv_exact(self, n: int, what: str = "frame") -> bytes:
        chunks = io.BytesIO()
        remaining = n
        while remaining:
            sock = self.sock
            if sock is None:
                raise ConnectionResetError("connection closed")
            # jaxlint: disable=unbounded-recv -- the framing layer's raw socket read: a dead peer raises, and a WEDGED peer is severed by the learner's heartbeat sweep (report_stale disconnects the socket, failing this recv)
            data = sock.recv(remaining)
            if not data:
                got = n - remaining
                if got:
                    # mid-frame close: the stream is corrupt, not
                    # merely finished
                    raise FrameError(
                        f"truncated {what}: peer closed after "
                        f"{got} of {n} bytes")
                raise ConnectionResetError("peer closed")
            chunks.write(data)
            remaining -= len(data)
        return chunks.getvalue()

    def recv(self) -> Any:
        (length,) = struct.unpack("!I", self._recv_exact(4, "header"))
        if length > self.max_frame_bytes:
            # validate BEFORE allocating: a garbage header must not
            # demand a multi-GiB buffer
            raise FrameError(
                f"frame length {length} exceeds max_frame_bytes "
                f"{self.max_frame_bytes} (corrupt header?)")
        return pickle.loads(self._recv_exact(length, "payload"))


class TracedConnection:
    """Trace-context codec over any connection duck type.

    Sends wrap the message in the telemetry envelope when the calling
    thread carries a trace context (untraced traffic stays
    byte-identical on the wire); recvs strip the envelope and adopt the
    sender's context into this thread.  Single-threaded owners only —
    the learner-side ``QueueCommunicator`` instead codecs at its own
    queue boundaries, because its recv thread is not the thread that
    handles the message.  Wrap AFTER process spawn (the wrapper holds
    no picklable state of its own, but the convention keeps ownership
    obvious): workers wrap their gather pipe, gathers wrap their
    learner connection (outside ChaosConnection, so injected faults
    hit enveloped frames like real ones)."""

    __slots__ = ("conn",)

    def __init__(self, conn):
        self.conn = conn

    def fileno(self):
        return self.conn.fileno()

    def close(self):
        return self.conn.close()

    def send(self, data: Any):
        self.conn.send(wrap_trace(data))

    def recv(self) -> Any:
        # jaxlint: disable=unbounded-recv -- transparent codec: blocking semantics (timeouts, supervision, heartbeat sweep) are the wrapped connection's property at each call site
        return unwrap_trace(self.conn.recv())

    def __getattr__(self, name):
        return getattr(self.conn, name)


# -- TCP helpers --------------------------------------------------------

def find_free_port() -> int:
    """An OS-assigned free TCP port (tests, local multihost bring-up)."""
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def open_socket_connection(address: str, port: int, reuse=False,
                           max_frame_bytes=DEFAULT_MAX_FRAME_BYTES):
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(
        socket.SOL_SOCKET, socket.SO_REUSEADDR,
        sock.getsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR) | 1,
    )
    sock.connect((address, port))
    return FramedConnection(sock, max_frame_bytes=max_frame_bytes)


def accept_socket_connections(port: int, timeout=None, backlog=128,
                              max_frame_bytes=DEFAULT_MAX_FRAME_BYTES):
    """Generator of connections; yields None on accept timeout so the
    caller's loop can check for shutdown.

    Accepts forever: workers are elastic and may churn indefinitely, so
    there is deliberately NO lifetime accept cap — live-connection
    bookkeeping belongs to the consumer (QueueCommunicator drops dead
    peers).  ``backlog`` only bounds the kernel's pending-accept queue."""
    server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        server.bind(("", port))
        server.listen(backlog)
        server.settimeout(timeout)
        while True:
            try:
                sock, _ = server.accept()
                yield FramedConnection(
                    sock, max_frame_bytes=max_frame_bytes)
            except socket.timeout:
                yield None
    finally:
        # runs on GeneratorExit when the consumer drops the generator:
        # the listening socket must not outlive its accept loop
        server.close()


# -- multiprocessing fan-out --------------------------------------------

# Child processes are SPAWNED, not forked: the parent owns a live TPU
# client (PJRT handles do not survive fork), so children start from a
# fresh interpreter and pin themselves to the CPU backend.
_mp = mp.get_context("spawn")


def force_cpu_jax():
    """A spawned child must choose the CPU before its first JAX call:
    the chip belongs to ONE process, the learner, and an actor/batcher
    child that let JAX pick would fail or hang reaching for it.  The
    variable covers a jax not yet imported (and this child's own
    children), the config update one already imported."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def open_multiprocessing_connections(num_procs: int,
                                     target: Callable,
                                     args_func: Callable[[int], tuple]):
    """Spawn ``num_procs`` daemon processes, each holding one end of a
    duplex pipe; returns the parent-side connections."""
    parent_conns = []
    for i in range(num_procs):
        parent, child = _mp.Pipe(duplex=True)
        proc = _mp.Process(
            target=target, args=(child,) + args_func(i), daemon=True
        )
        proc.start()
        child.close()
        parent_conns.append(parent)
    return parent_conns


class MultiProcessJobExecutor:
    """Farm (send job -> recv result) over worker processes.

    ``func(conn, *args)`` runs in each child and is expected to loop
    ``recv -> work -> send``.  The parent pushes jobs round-robin from
    ``send_generator`` whenever a worker's slot frees, keeping
    ``num_receivers`` threads draining results into a bounded queue —
    the same overlap structure the reference uses for its batcher farm
    (/root/reference/handyrl/connection.py:133-173).
    """

    def __init__(self, func, send_generator, num_workers,
                 postprocess=None, out_maxsize: int = 8,
                 args_func: Callable[[int], tuple] = lambda i: ()):
        self.send_generator = send_generator
        self.postprocess = postprocess
        self.conns = open_multiprocessing_connections(
            num_workers, func, args_func
        )
        self.waiting_conns = queue.Queue()
        for conn in self.conns:
            self.waiting_conns.put(conn)
        self.output_queue = queue.Queue(maxsize=out_maxsize)
        self.shutdown_flag = False
        self.threads = []

    def shutdown(self):
        self.shutdown_flag = True

    def recv(self, timeout=None):
        return self.output_queue.get(timeout=timeout)

    def start(self):
        self.threads.append(
            threading.Thread(target=self._sender, daemon=True))
        self.threads.append(
            threading.Thread(target=self._receiver, daemon=True))
        for t in self.threads:
            t.start()

    def _sender(self):
        while not self.shutdown_flag:
            try:
                # bounded wait so shutdown() actually releases this
                # thread (a bare .get() would park it forever once the
                # receiver stops returning conns — commlint
                # unbounded-recv found exactly that wedge)
                conn = self.waiting_conns.get(timeout=0.3)
            except queue.Empty:
                continue
            conn.send(next(self.send_generator))

    def _receiver(self):
        while not self.shutdown_flag:
            ready = mp.connection.wait(self.conns, timeout=0.3)
            for conn in ready:
                try:
                    # jaxlint: disable=unbounded-recv -- wait() selected this conn: a message is pending
                    data = conn.recv()
                except EOFError:
                    continue
                self.waiting_conns.put(conn)
                if self.postprocess is not None:
                    data = self.postprocess(data)
                self.output_queue.put(data)


class QueueCommunicator:
    """Async request hub over a mutable set of connections.

    Receives from every registered connection into ``input_queue`` as
    ``(conn, data)`` pairs; ``send_queue`` drains in a writer thread.
    Dead peers (reset/EOF) are dropped — workers are elastic, they can
    connect and vanish at any time (parity with
    /root/reference/handyrl/connection.py:176-224 and the elastic-join
    design in /root/reference/docs/large_scale_training.md:34).
    """

    def __init__(self, conns: Iterable = ()):
        self.input_queue = queue.Queue(maxsize=256)
        self.output_queue = queue.Queue(maxsize=256)
        self.conns: Dict[Any, bool] = {}
        self._lock = threading.Lock()
        # observability for the FleetRegistry: replies dropped because
        # their peer died first, and peer-disconnect events
        self.send_drops = 0
        self.disconnects = 0
        # runtime counterpart of commlint's unhandled-verb: requests
        # whose verb no server handler knows, counted per verb name
        self.unknown_verbs: Dict[str, int] = {}
        # StallWatchdog beat callable (set by the learner): the writer
        # and reader threads prove liveness once per loop pass
        self.liveness_hook = None
        for conn in conns:
            self.add_connection(conn)
        self.shutdown_flag = False
        self.threads = [
            threading.Thread(target=self._send_loop, daemon=True),
            threading.Thread(target=self._recv_loop, daemon=True),
        ]
        for t in self.threads:
            t.start()

    def shutdown(self):
        self.shutdown_flag = True

    def connection_count(self):
        return len(self.conns)

    def live_connections(self):
        with self._lock:
            return list(self.conns)

    def recv(self, timeout=None):
        # the envelope codec runs HERE, not in the reader thread: the
        # thread that handles the message is the one that must adopt
        # (or clear) the sender's trace context
        conn, data = self.input_queue.get(timeout=timeout)
        return conn, unwrap_trace(data)

    def send(self, conn, send_data):
        # wrap in the caller's thread for the same reason: a reply
        # enqueued while a request's context is current carries it
        self.output_queue.put((conn, wrap_trace(send_data)))

    def note_unknown_verb(self, verb):
        """An arriving request named a verb no handler knows.  Counted
        per verb (surfaced as ``unknown_verbs`` in :meth:`drop_stats`
        and the fleet metrics) and logged ONCE per verb name — a
        version-skewed worker fleet can send thousands of these, and
        the first line says everything the next ones would."""
        verb = str(verb)
        with self._lock:
            count = self.unknown_verbs.get(verb, 0)
            self.unknown_verbs[verb] = count + 1
        if count == 0:
            print(f"WARNING: unknown control-plane verb {verb!r} "
                  f"(version skew or a stray client?); replying empty "
                  f"— further occurrences counted silently")

    def drop_stats(self) -> Dict[str, int]:
        """Drop counters for the learner's FleetRegistry / metrics.

        Snapshot taken under the counters' lock: the status HTTP
        thread calls this while the send/recv loops are bumping the
        counters, and a bare read could pair a pre-update
        ``send_drops`` with a post-update ``disconnects`` (or iterate
        ``unknown_verbs`` mid-insert)."""
        with self._lock:
            return {"send_drops": self.send_drops,
                    "disconnects": self.disconnects,
                    "unknown_verbs": sum(self.unknown_verbs.values())}

    def fleet_stats(self) -> Dict[str, int]:
        """Fleet-health contribution for the per-epoch metrics record;
        supervised subclasses add respawn/alive counts."""
        return self.drop_stats()

    def begin_drain(self):
        """Shutdown is coming: child exits are expected from here on.
        No-op at this level; supervised subclasses stop respawning."""

    def report_stale(self, conn):
        """A peer missed its heartbeats.  No-op at this level (remote
        peers are dropped when their socket dies); supervised
        subclasses evict the wedged child so it respawns."""

    def _send_loop(self):
        while not self.shutdown_flag:
            hook = self.liveness_hook
            if hook is not None:
                hook("send_loop")
            try:
                conn, send_data = self.output_queue.get(timeout=0.3)
            except queue.Empty:
                continue
            with self._lock:
                live = conn in self.conns
                if not live:
                    # the peer died between enqueue and write: drop
                    # and count instead of feeding the daemon thread
                    # an exception on a closed handle
                    self.send_drops += 1
            if not live:
                continue
            try:
                conn.send(send_data)
            except (ConnectionResetError, BrokenPipeError, OSError):
                with self._lock:
                    self.send_drops += 1
                self.disconnect(conn)

    def add_connection(self, conn):
        with self._lock:
            self.conns[conn] = True

    def disconnect(self, conn):
        # the counter bump shares the pop's critical section: both the
        # send loop and the recv loop disconnect dead peers, and two
        # unlocked += on the same counter can lose one
        with self._lock:
            removed = self.conns.pop(conn, None) is not None
            if removed:
                self.disconnects += 1
        try:
            conn.close()
        except OSError:
            pass

    def _recv_loop(self):
        while not self.shutdown_flag:
            hook = self.liveness_hook
            if hook is not None:
                hook("recv_loop")
            with self._lock:
                conns = list(self.conns)
            if not conns:
                time.sleep(0.1)
                continue
            try:
                ready = mp.connection.wait(conns, timeout=0.3)
            except OSError:
                ready = []
            for conn in ready:
                try:
                    # jaxlint: disable=unbounded-recv -- wait() selected this conn: a frame is pending (a peer dying mid-frame raises, it does not block)
                    data = conn.recv()
                except (ConnectionResetError, BrokenPipeError, EOFError,
                        OSError):
                    self.disconnect(conn)
                    continue
                while not self.shutdown_flag:
                    try:
                        self.input_queue.put((conn, data), timeout=0.3)
                        break
                    except queue.Full:
                        continue
