"""Control-plane persistence — the Redis-backed GCS storage equivalent.

Parity with the reference's pluggable GCS store (ray:
src/ray/gcs/store_client/store_client.h — the StoreClient interface;
src/ray/gcs/store_client/redis_store_client.h:33 the external backend
behind GcsTableStorage; selection at gcs_server.cc:517-518): the
control plane's durable tables (KV, detached-actor creation specs,
placement-group specs) snapshot through a :class:`StoreClient`.

Backends:

* :class:`FileStore` — atomic local snapshot (tmp + rename); a crash
  loses at most one flush period of writes — Redis "appendfsync
  everysec" semantics.  Survives head PROCESS loss.
* :class:`MirroredStore` — a primary plus replica stores, written
  best-effort on every flush.  With a replica on another failure
  domain (a peer machine's export, an NFS/GCS-bucket mount), the
  control plane survives head MACHINE loss: bootstrap loads the
  NEWEST readable snapshot across primary + mirrors, so a head
  restarted on a fresh machine with only the mirror reachable
  recovers its tables (the Redis deployment's role, without requiring
  a Redis in the image).

A driver/head restart pointed at the same store rebuilds the tables
(gcs_init_data.cc replays tables the same way).
"""

from __future__ import annotations

import contextlib
import os
import pickle
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence

from ray_tpu.util import tracing

_FORMAT_VERSION = 2


class StoreClient:
    """Minimal durable-snapshot interface (parity:
    src/ray/gcs/store_client/store_client.h, narrowed to the snapshot
    granularity this control plane persists at)."""

    def load_blob(self) -> Optional[Dict[str, Any]]:
        raise NotImplementedError

    def save_blob(self, blob: Dict[str, Any]) -> None:
        raise NotImplementedError

    def describe(self) -> str:
        raise NotImplementedError


class FileStore(StoreClient):
    """Atomic snapshot file (tmp + fsync + rename)."""

    def __init__(self, path: str):
        self.path = path

    def load_blob(self) -> Optional[Dict[str, Any]]:
        try:
            with open(self.path, "rb") as f:
                blob = pickle.load(f)
        except Exception:
            # OSError, UnpicklingError, but also AttributeError/
            # ImportError/ValueError from foreign or corrupt pickles —
            # any unreadable snapshot means "no data here", never
            # "fail init" (recovery is the whole point).
            return None
        if not isinstance(blob, dict):
            return None
        if blob.get("version") == 1 and "tables" in blob:
            # v1 (pre-mirror) snapshots carry no seq/saved_at: migrate
            # in place rather than silently dropping a cluster's
            # persisted control plane on upgrade.
            return {"version": _FORMAT_VERSION, "seq": 0,
                    "saved_at": 0.0, "tables": blob["tables"]}
        if blob.get("version") != _FORMAT_VERSION:
            return None
        return blob

    def save_blob(self, blob: Dict[str, Any]) -> None:
        d = os.path.dirname(os.path.abspath(self.path)) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".gcs-snap-")
        try:
            with os.fdopen(fd, "wb") as f:
                pickle.dump(blob, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def describe(self) -> str:
        return f"file:{self.path}"


class MirroredStore(StoreClient):
    """Primary + best-effort replicas; loads pick the NEWEST readable
    snapshot (each blob carries a monotonic save counter + wall time),
    so bootstrap works from whichever copy survived."""

    def __init__(self, primary: StoreClient,
                 mirrors: Sequence[StoreClient]):
        self.primary = primary
        self.mirrors = list(mirrors)
        self._warned: set = set()

    def load_blob(self) -> Optional[Dict[str, Any]]:
        candidates = []
        for store in [self.primary] + self.mirrors:
            blob = store.load_blob()
            if blob is not None:
                candidates.append(blob)
        if not candidates:
            return None
        # Seq dominates, wall time breaks ties: the save counter is
        # resumed from the restored blob on restart, so it is monotonic
        # across head generations — unlike saved_at, which a replacement
        # head with a skewed (or stepped-back) clock can stamp EARLIER
        # than a genuinely stale copy, silently restoring a dead
        # generation that resurrects deleted actors and drops recent
        # writes.  saved_at only arbitrates between copies of the same
        # seq (e.g. a mirror that got the write and a primary that got
        # re-written after a partial failure).
        return max(candidates,
                   key=lambda b: (b.get("seq", 0), b.get("saved_at", 0)))

    def _warn_once(self, store: StoreClient, err: Exception,
                   role: str) -> None:
        key = store.describe()
        if key not in self._warned:
            self._warned.add(key)
            import logging

            logging.getLogger("ray_tpu.gcs").warning(
                "GCS %s store %s is failing (%r) — snapshot "
                "durability is degraded until it recovers", role, key,
                err)

    def save_blob(self, blob: Dict[str, Any]) -> None:
        # Every store is written INDEPENDENTLY — a dead primary (the
        # exact head-disk failure mirroring exists for) must not stop
        # the replicas from advancing.  Each failing store WARNS once;
        # the save as a whole fails only when NO copy persisted.
        first_err: Optional[Exception] = None
        ok = 0
        for role, store in [("primary", self.primary)] + [
                ("mirror", m) for m in self.mirrors]:
            try:
                store.save_blob(blob)
                ok += 1
                self._warned.discard(store.describe())
            except Exception as e:
                if first_err is None:
                    first_err = e
                self._warn_once(store, e, role)
        if ok == 0 and first_err is not None:
            raise first_err

    def describe(self) -> str:
        return " + ".join(s.describe()
                          for s in [self.primary] + self.mirrors)


class KvStoreClient(StoreClient):
    """Snapshot blob stored as one pickled value in the cluster KV.

    The runtime KV lives on the driver's runtime instance, so it
    survives any ACTOR's death (the serve controller checkpoints through
    this), and it is itself disk-persisted by :class:`GcsPersistence`
    when ``gcs_persist_path`` is configured — a checkpoint written here
    inherits whatever durability tier the cluster's GCS storage has.
    Unlike :class:`FileStore`, a present-but-unreadable blob is reported
    loudly: the value existed, so silence would hide corruption.
    """

    def __init__(self, kv, namespace: str = "serve",
                 key: bytes = b"controller::checkpoint"):
        self._kv = kv
        self.namespace = namespace
        self.key = key if isinstance(key, bytes) else key.encode()

    def _warn(self, why: str) -> None:
        import logging

        logging.getLogger("ray_tpu.gcs").warning(
            "GCS store %s holds an unreadable snapshot (%s) — treating "
            "it as absent", self.describe(), why)

    def load_blob(self) -> Optional[Dict[str, Any]]:
        raw = self._kv.get(self.key, namespace=self.namespace)
        if raw is None:
            return None
        try:
            blob = pickle.loads(raw)
        except Exception as e:
            self._warn(f"corrupt pickle: {e!r}")
            return None
        if not isinstance(blob, dict):
            self._warn(f"not a snapshot dict: {type(blob).__name__}")
            return None
        if blob.get("version") != _FORMAT_VERSION:
            self._warn(f"format version {blob.get('version')!r} != "
                       f"{_FORMAT_VERSION}")
            return None
        return blob

    def save_blob(self, blob: Dict[str, Any]) -> None:
        self._kv.put(self.key, pickle.dumps(blob),
                     namespace=self.namespace)

    def describe(self) -> str:
        return f"kv:{self.namespace}/{self.key.decode(errors='replace')}"


def make_store(path: str, mirror_paths: Sequence[str] = ()) -> StoreClient:
    """Store from config strings (parity: gcs_server.cc:517-518
    choosing the storage backend from flags)."""
    primary = FileStore(path)
    mirrors = [FileStore(p) for p in mirror_paths if p]
    if mirrors:
        return MirroredStore(primary, mirrors)
    return primary


class GcsPersistence:
    """Snapshot + dirty-flag flusher thread over a StoreClient."""

    def __init__(self, path: str, flush_period_s: float = 0.2,
                 mirror_paths: Sequence[str] = (),
                 store: Optional[StoreClient] = None):
        # An explicit store (e.g. KvStoreClient, or a MirroredStore over
        # one) bypasses path-based construction — the serve controller's
        # checkpointer reuses this flusher over the cluster KV.
        self.store = store if store is not None \
            else make_store(path, mirror_paths)
        self.path = path
        self._period = flush_period_s
        self._dirty = threading.Event()
        self._stop = threading.Event()
        # Serializes saves: the final flush must never lose to a stale
        # in-flight periodic save's os.replace.
        self._save_lock = threading.Lock()
        self._seq = 0
        self._collect: Optional[Callable[[], Dict[str, Any]]] = None
        self._span_name: Optional[str] = None
        self._thread: Optional[threading.Thread] = None

    # -- load --------------------------------------------------------------

    def load(self) -> Optional[Dict[str, Any]]:
        """The newest readable snapshot's tables, or None."""
        blob = self.store.load_blob()
        if blob is None:
            return None
        # Resume the save counter past the restored snapshot so a
        # restart's snapshots outrank the old generation on mirrors.
        self._seq = int(blob.get("seq", 0))
        return blob.get("tables")

    # -- save --------------------------------------------------------------

    def save(self, tables: Dict[str, Any]) -> None:
        self._seq += 1
        self.store.save_blob({
            "version": _FORMAT_VERSION,
            "seq": self._seq,
            "saved_at": time.time(),
            "tables": tables,
        })

    # -- flusher -----------------------------------------------------------

    def start_flusher(self, collect: Callable[[], Dict[str, Any]],
                      span_name: Optional[str] = None) -> None:
        """``span_name``: each periodic flush runs inside this span
        (util/tracing), so a profiler capture of the process shows
        when the flusher held the interpreter."""
        self._collect = collect
        self._span_name = span_name
        self._thread = threading.Thread(
            target=self._flush_loop, name="gcs-flush", daemon=True
        )
        self._thread.start()

    def mark_dirty(self) -> None:
        self._dirty.set()

    def _flush_loop(self) -> None:
        while not self._stop.wait(self._period):
            if self._dirty.is_set():
                self._dirty.clear()
                self._try_flush()

    def _try_flush(self) -> None:
        try:
            span = (tracing.span(self._span_name, record=False)
                    if self._span_name else contextlib.nullcontext())
            with span, self._save_lock:
                self.save(self._collect())
        except Exception:
            pass  # persistence is best-effort; next tick retries

    def close(self, final_flush: bool = True) -> None:
        self._stop.set()
        if self._thread is not None:
            # Join BEFORE the final flush: an in-flight periodic save
            # could otherwise rename its stale snapshot over the final
            # one and silently lose the last writes.  If it is stuck
            # (hung filesystem), the save lock still orders us after it
            # — bounded, so a truly hung fsync can't wedge shutdown.
            self._thread.join(timeout=5.0)
        if final_flush and self._collect is not None:
            if self._save_lock.acquire(timeout=10.0):
                try:
                    self.save(self._collect())
                except Exception:
                    pass
                finally:
                    self._save_lock.release()
