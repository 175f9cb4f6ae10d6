"""Compile the served path's Pallas kernels for a TPU v5e, without a chip.

The TPU compiler is installed next to JAX, so it compiles for a chip that
is described and not attached.  Each test lowers ``ivf_topk`` or
``slab_topk`` at the served widths (d=768, k=10, slabs of 4096 rows) and
asserts that the compiled program holds the Mosaic kernel
(``tpu_custom_call``).  What interpret mode accepts and Mosaic refuses
(gathers, integer argmin, dynamic-lane stores, fp16 loads) fails here.

``test_decode_step_reads_the_kv_cache_in_place`` compiles the generator's
decode step at stablelm-2-1.6b's widths and reads the compiled program.

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, so under pytest-xdist
only the worker that runs this file may touch it.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import configs
from repro.kernels.ivf_topk.kernel import topk_ip_pallas
from repro.kernels.slab_topk.kernel import slab_topk_pallas

D, K, N, M = 768, 10, 4096, 48          # gte width, top-10, slab rows, PQ m


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back here: keep the cache off for this module
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


@pytest.mark.parametrize("q", [1, 16])
def test_ivf_topk_compiles(one_chip, q):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(lambda e, qs: topk_ip_pallas(e, qs, K, interpret=False),
             s((N, D), jnp.float32), s((q, D), jnp.float32))


@pytest.mark.parametrize("q", [1, 16])
@pytest.mark.parametrize("tier", ["fp32", "int8", "pq"])
def test_slab_topk_compiles(one_chip, tier, q):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    queries, virt = s((q, D), jnp.float32), s((q, N), jnp.int32)
    if tier == "fp32":
        _compile(lambda e, qs, v: slab_topk_pallas(e, qs, v, K,
                                                   interpret=False),
                 s((N, D), jnp.float32), queries, virt)
    elif tier == "int8":
        _compile(lambda e, qs, v, sc: slab_topk_pallas(e, qs, v, K, sc,
                                                       interpret=False),
                 s((N, D), jnp.int8), queries, virt, s((N, 1), jnp.float32))
    else:
        _compile(lambda c, qs, v, lut: slab_topk_pallas(
                     c, qs, v, K, None, lut, interpret=False),
                 s((N, M), jnp.uint8), queries, virt,
                 s((q, M, 256), jnp.float32))


def test_slab_topk_fp16_refuses_to_compile(one_chip):
    """fp16 slab blocks do not load on TPU: an explicit error, never a
    silent widen to fp32 or a fall back to the reference."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    with pytest.raises(NotImplementedError, match="fp16"):
        _compile(lambda e, qs, v: slab_topk_pallas(e, qs, v, K,
                                                   interpret=False),
                 s((N, D), jnp.float16), s((16, D), jnp.float32),
                 s((16, N), jnp.int32))


def test_sharded_slab_topk_compiles_for_four_chips(topo):
    """The four-chip route: slab and virt row-sharded over a 2x2 mesh, one
    all-gather of per-shard candidates."""
    from repro.core.sharded_retrieval import sharded_slab_topk
    mesh = jax.sharding.Mesh(np.array(topo.devices), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    cols = NamedSharding(mesh, P(None, "data"))
    rep = NamedSharding(mesh, P())
    compiled = jax.jit(
        lambda e, qs, v: sharded_slab_topk(e, qs, v, K, mesh)).lower(
        jax.ShapeDtypeStruct((N, D), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((16, D), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((16, N), jnp.int32, sharding=cols)).compile()
    assert "all-gather" in compiled.as_text()


def _computation(text: str, name: str) -> str:
    start = text.index(f"\n%{name} ")
    return text[start:text.index("\n}\n", start)]


def _results(text: str, elements: int):
    """(name, opcode) of each instruction whose float32 result holds
    ``elements`` numbers."""
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = f32\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if m and math.prod(int(d) for d in m.group(2).split(",") if d) \
                == elements:
            out.append((m.group(1), m.group(3)))
    return out


def test_decode_step_reads_the_kv_cache_in_place(one_chip):
    """stablelm-2-1.6b's decode step (two of its layers; a cache of 2,048
    prompt and 16 answer positions, float32): the layer loop's body holds
    no instruction whose result is a whole layer's K or V (a slice, copy,
    transpose or update of it), and no copy of the stacked cache surrounds
    the loop.  Each layer is read where it lies, and the step's rows go
    into the stack in place."""
    from repro.models import decode_step, init_cache, init_params
    cfg = dataclasses.replace(configs.get_config("stablelm-1.6b"),
                              num_layers=2)
    positions = 2048 + 16
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    caches = jax.eval_shape(lambda: init_cache(cfg, 1, positions))
    text = jax.jit(lambda p, t, c, n: decode_step(p, cfg, t, c, n),
                   donate_argnums=2).lower(
        jax.tree.map(on_chip, params),
        on_chip(jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        jax.tree.map(on_chip, caches),
        on_chip(jax.ShapeDtypeStruct((), jnp.int32))).compile().as_text()
    layer = positions * cfg.num_kv_heads * cfg.head_dim
    body = re.search(r"while\(.*?body=%([\w.\-]+)", text).group(1)
    assert _results(_computation(text, body), layer) == []
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}\n")]
    stack = [op for _, op in _results(entry, cfg.num_layers * layer)]
    assert "copy" not in stack and "transpose" not in stack
    assert stack.count("dynamic-update-slice") == 2, stack
