"""ray_tpu — a TPU-native distributed computing framework.

Capabilities modeled on Ray (see SURVEY.md for the reference blueprint):
tasks, actors, a shared-memory object store, placement groups and an
ICI-topology-aware scheduler — with jax/XLA-first ML libraries on top
(parallel meshes, Pallas ops, models, train, data, serve, tune).

Subpackage map:
  ray_tpu.core      tasks / actors / objects runtime (reference: src/ray + python/ray/_private)
  ray_tpu.parallel  device meshes, sharding rules, collectives (reference: util/collective + Train backends)
  ray_tpu.ops       Pallas TPU kernels (no reference counterpart — TPU-first)
  ray_tpu.models    flagship model families (Llama, Mixtral, ViT, Mamba)
  ray_tpu.train     distributed training harness (reference: python/ray/train)
  ray_tpu.data      streaming datasets (reference: python/ray/data)
  ray_tpu.serve     continuous-batched inference (reference: python/ray/serve)
  ray_tpu.tune      experiment runner (reference: python/ray/tune)
"""

__version__ = "0.1.0"

import time as _time

# Top and bottom of this import on the wall clock, for the start-up
# record's ``import{ray_tpu}``.  Importing util/tracing here would pull
# the core runtime in with ``ray_tpu.util`` and `import ray_tpu` would
# stop being light, so tracing writes the event when it is first
# imported (any process that starts anything imports it).
_import_times = [_time.time(), None]

from ray_tpu.utils.ids import ActorID, JobID, NodeID, ObjectID, TaskID

_API = None


def _api():
    """Lazy import of the core runtime so `import ray_tpu` stays light."""
    global _API
    if _API is None:
        from ray_tpu.core import api as _core_api

        _API = _core_api
    return _API


def init(*args, **kwargs):
    return _api().init(*args, **kwargs)


def shutdown(*args, **kwargs):
    return _api().shutdown(*args, **kwargs)


def is_initialized():
    return _api().is_initialized()


def remote(*args, **kwargs):
    return _api().remote(*args, **kwargs)


def get(refs, *, timeout=None):
    return _api().get(refs, timeout=timeout)


def put(value):
    return _api().put(value)


def wait(refs, *, num_returns=1, timeout=None, fetch_local=True):
    return _api().wait(
        refs, num_returns=num_returns, timeout=timeout, fetch_local=fetch_local
    )


def kill(actor, *, no_restart=True):
    return _api().kill(actor, no_restart=no_restart)


def get_actor(name: str):
    return _api().get_actor(name)


def cancel(ref, *, force=False):
    return _api().cancel(ref, force=force)


def method(**kwargs):
    return _api().method(**kwargs)


def nodes():
    return _api().nodes()


def placement_group_table():
    return _api().runtime().placement_group_table()


def timeline(filename=None):
    """Chrome-trace dump of recorded task events (parity: ray.timeline)."""
    from ray_tpu.util import state as _state

    return _state.timeline(filename)


def cluster_resources():
    return _api().cluster_resources()


def available_resources():
    return _api().available_resources()


_import_times[1] = _time.time()
