"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that is
described, not attached, and refuses what the chip would refuse (unaligned
blocks, loads from the wrong memory space, more VMEM than a kernel may use).
Each compile takes a second or two.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so a module that did it at
import would break every other test worker.  All such compiles live in this
one file for the same reason.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ihb as ihb_mod
from repro.core.oavi import OAVIConfig, _make_degree_step
from repro.core.svm import _fista
from repro.kernels.gram_update import gram_update_acc
from repro.kernels.ihb_update import ihb_update
from repro.kernels.svm_grad import LANES, block_rows, svm_grad

F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    def make(shape, dtype=F32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,L,K", [(3, 64, 64), (57, 256, 512), (57, 2048, 2048)])
def test_gram_update_acc_compiles(sds, n, L, K):
    bm, blocks = 256, 4
    m = bm * blocks
    fn = jax.jit(lambda *a: gram_update_acc(*a, bm=bm))
    compiled = fn.lower(
        sds((m, L)), sds((m, n)), sds((L, K)), sds((n, K)), sds((L, K)), sds((K, K))
    ).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("L", [64, 2048])
def test_ihb_update_compiles(sds, L):
    compiled = ihb_update.lower(
        sds((L, L)), sds((L,)), sds(()), sds((), I32)
    ).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("k,L", [(8, 1024), (2, 2048)])
def test_ihb_update_vmapped_compiles(sds, k, L):
    compiled = jax.jit(jax.vmap(ihb_update)).lower(
        sds((k, L, L)), sds((k, L)), sds((k,)), sds((k,), I32)
    ).compile()
    assert _has_kernel(compiled)


def test_degree_step_with_pallas_kernels_compiles(sds):
    """One whole in-memory degree step (fast engine, both kernels forced):
    the Gram kernel and the IHB update both appear as Mosaic custom calls."""
    cfg = OAVIConfig(kernel="pallas")
    m, n, Lcap, Kcap = 4096, 3, 64, 64
    state = jax.eval_shape(
        lambda: ihb_mod.init_state(Lcap, jnp.asarray(1.0, F32), F32,
                                   factors=cfg.ihb_factors())
    )
    state = jax.tree.map(lambda a: sds(a.shape, a.dtype), state)
    compiled = jax.jit(_make_degree_step(cfg)).lower(
        sds((m, Lcap)), sds((m, n)), state, sds((), I32),
        sds((Kcap,), I32), sds((Kcap,), I32), sds((Kcap,), jnp.bool_), sds(()),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2, "expected the Gram and IHB kernels"


def test_class_batched_wide_border_degree_step_compiles(sds):
    """The spam deployment's degree 2 as the class-batched fit dispatches
    it: two classes, n = 57, Lcap = Kcap = 2048 (a border of 1,653), the
    CG oracle's while_loop under the vmap, both kernels forced."""
    import dataclasses

    from repro.configs import oavi_paper

    cfg = dataclasses.replace(oavi_paper.cgavi_ihb(), kernel="pallas")
    k, m, n, Lcap, Kcap = 2, 2048, 57, 2048, 2048
    state = jax.eval_shape(
        lambda: ihb_mod.batch_state(
            ihb_mod.init_state(Lcap, jnp.asarray(1.0, F32), F32, factors=cfg.ihb_factors()), k)
    )
    state = jax.tree.map(lambda a: sds(a.shape, a.dtype), state)
    step = _make_degree_step(cfg)
    compiled = jax.jit(jax.vmap(step)).lower(
        sds((k, m, Lcap)), sds((k, m, n)), state, sds((k,), I32),
        sds((k, Kcap), I32), sds((k, Kcap), I32), sds((k, Kcap), jnp.bool_), sds((k,)),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2, "expected the Gram and IHB kernels"


# (m, p): the SVM features of the benchmark's cells, Appendix C and credit
_SVM_SHAPES = [(1_200_000, 12), (18_000, 506)]


@pytest.mark.parametrize("m,p", _SVM_SHAPES)
def test_svm_grad_compiles(sds, m, p):
    k = 2
    _, R = block_rows(p, k, m)
    compiled = jax.jit(lambda *a: svm_grad(*a, m=m)).lower(
        sds((p, k)), sds((k,)), sds((p, R, LANES)), sds((k, R, LANES))
    ).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("m,p", _SVM_SHAPES)
def test_fista_with_svm_grad_compiles(sds, m, p):
    """The whole FISTA while loop with the gradient kernel: the kernel is in
    it, and nothing copies the feature array (XLA's jnp loop copies all of
    it into VMEM in every iteration)."""
    k = 2
    _, R = block_rows(p, k, m)
    compiled = jax.jit(
        lambda X, Y, lam, step, tol: _fista(X, Y, lam, step, 10_000, tol, m=m, use_pallas=True)
    ).lower(sds((p, R, LANES)), sds((k, R, LANES)), sds(()), sds(()), sds(())).compile()
    text = compiled.as_text()
    assert _has_kernel(compiled) and " while(" in text
    copies_features = re.compile(re.escape(f"f32[{p},{R},{LANES}]") + r"[^=]* copy(-start)?\(")
    assert not copies_features.search(text)
