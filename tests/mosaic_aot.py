"""What tests/test_mosaic_aot*.py share: a v5e topology without a chip,
abstract arguments placed on it, and the compile.

libtpu can describe a topology it does not have
(``jax.experimental.topologies``), and ``jit(...).trace(...).lower(
lowering_platforms=("tpu",)).compile()`` then runs the real Mosaic and
XLA:TPU compilers against abstract arguments placed on that topology's
devices.  Every other test runs the kernels through the Pallas
interpreter, which accepts programs Mosaic refuses; those files are the
off-chip guard that the programs ``chip_smoke.py`` runs still compile,
at the widths the benchmark uses, on one device and on four.  They cost
no chip time.  What they cannot see is a wrong answer: that is the
smoke's ``kernels`` phase.

Four files, by what they compile, so that no worker of the tier-1 run
has all of it: the kernels alone (``test_mosaic_aot.py``), the benchmark
cells' step programs (``test_mosaic_aot_cells.py`` and, for the two with
routed experts, ``test_mosaic_aot_cells_moe.py``), the ``bench.py``
shaped steps and the meshes (``test_mosaic_aot_steps.py``).  Each asks
libtpu for the topology in a process of its own, which
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` (tests/conftest.py) allows.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import llama, quant
from ray_tpu.ops import platform

REPO = pathlib.Path(__file__).resolve().parent.parent
PAGE = 64


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def v5e():
    return topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu").devices


@pytest.fixture(autouse=True)
def mosaic_not_interpreter(monkeypatch):
    monkeypatch.setattr(platform, "interpret_mode", lambda: False)


def _on(mesh, tree, spec=P()):
    """Abstract arguments placed on ``mesh`` (replicated unless told)."""
    sh = NamedSharding(mesh, spec)
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)


def _compile(fn, *args, mesh=None, **jit_kw):
    with mesh if mesh is not None else contextlib.nullcontext():
        return (jax.jit(fn, **jit_kw).trace(*args)
                .lower(lowering_platforms=("tpu",)).compile())


def _one(v5e):
    return Mesh(np.array(v5e[:1]), ("x",))


def _sds(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _pallas_grids(jaxpr, name):
    """The grid of every ``pallas_call`` called ``name`` anywhere in
    ``jaxpr``; a bound the call takes as an operand reads None."""
    found = []
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_grids(sub, name)
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == name):
            found.append(tuple(
                b if isinstance(b, int) else None
                for b in eqn.params["grid_mapping"].grid))
    return found


def _assert_fused_layer_grid_follows_the_rows(step, *args):
    """The fused layer kernel's one grid bound is an operand of the call
    (the step's live cells plus the weight tiles), not the page table's
    capacity: a fall-back to the static bound on the chip fails here."""
    grids = _pallas_grids(jax.make_jaxpr(step)(*args).jaxpr,
                          "fused_ragged_layer")
    assert grids == [(None,)], grids


def _step_shapes(token_budget, max_slots):
    """The two shapes ``LLMEngine`` compiles of the ragged step."""
    from ray_tpu.serve.llm_engine import ragged_step_shapes

    small, budget = ragged_step_shapes(token_budget, max_slots)
    return {"budget": budget, "small": small}


def _chat_cell(v5e):
    """``mistral7b_w8-chat`` as the benchmark runs it, abstract on one
    v5e device: the model config, the engine's settings, the mesh, the
    fused int8 artifact and the int8 page cache."""
    from benchmarks.runners.common import model_config

    config = json.loads(
        (REPO / "benchmarks/configs/mistral7b_w8.json").read_text())
    cfg, eng = model_config(config), config["engine"]
    assert cfg.fused_decode and cfg.kv_int8
    mesh = _one(v5e)
    params = _on(mesh, jax.eval_shape(
        lambda: quant.fuse_for_decode(
            quant.init_quantized_llama(jax.random.key(0), cfg), cfg)))
    cache = _on(mesh, jax.eval_shape(
        lambda: llama.init_paged_cache(cfg, eng["num_pages"],
                                       eng["page_size"])))
    return cfg, eng, mesh, params, cache


# -- Jamba: the selective-scan kernel and the cell's whole step ---------------

def _jamba_cell():
    from benchmarks.runners import serve_jamba

    config = json.loads(
        (REPO / "benchmarks/configs/jamba2_3b.json").read_text())
    eng = config["engine"]
    T = eng["max_slots"] + max(eng["prefill_chunk"], eng["page_size"])
    return serve_jamba.model_config(config), eng, T


# -- the Brumby cell: power retention, state by slot and no page -------------

def _brumby_cell():
    from benchmarks.runners.common import model_config

    config = json.loads(
        (REPO / "benchmarks" / "configs" / "brumby14b_pp4.json").read_text())
    eng = config["engine"]
    return model_config(config), eng, eng["max_slots"] + eng["prefill_chunk"]


# -- the Xing cell: latent page pool, routed experts, four-stream residual ---

def _xing_cell():
    from benchmarks.runners.serve_xing import model_config

    config = json.loads(
        (REPO / "benchmarks" / "configs" / "xing4_29b_pp8.json").read_text())
    eng = config["engine"]
    return model_config(config), eng, eng["max_slots"] + eng["prefill_chunk"]
