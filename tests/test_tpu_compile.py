"""Compile the engine's Pallas kernels for a described TPU v5e chip.

Interpret mode (every other test here) runs a kernel's BlockSpec and grid
logic but not Mosaic, which refuses relayouts, unaligned slices and
primitives such as scatter that the interpreter accepts.  These tests
compile each kernel on the engine's main path for one chip of a described
``v5e:2x2`` topology, with ``interpret=False``, at ``chip_smoke.py``'s
widths (32 f32 columns, k = 10 clusters, a one-hot design of 24/16/8
levels), one 64 MiB I/O partition of rows, and the ``block_rows`` the
engine's lowering hands the kernel.  Nothing runs: no chip is needed.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker that runs them
loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fm
from repro.core.fusion import Plan
from repro.kernels import fused_apply_agg as faa
from repro.kernels import gram as gram_mod
from repro.kernels import kmeans_assign as ka
from repro.kernels import spmm
from repro.kernels import weighted_gram as wg

ROWS = 1 << 19            # one 64 MiB I/O partition of 32 f32 columns
P = 32
K = 10
LEVELS = (24, 16, 8)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _unit(*outs):
    """The single Pallas kernel unit the engine lowers ``outs`` onto."""
    units = Plan([o.m for o in outs]).program("pallas").kernel_units
    assert len(units) == 1, [u.kernel for u in units]
    return units[0]


def _dense(p=P, dtype=np.float32, n=256):
    return fm.conv_R2FM(np.arange(n * p).reshape(n, p).astype(dtype) % 7)


def _one_hot(n=256):
    return fm.one_hot(*[fm.as_factor(np.arange(n) % lv, lv)
                        for lv in LEVELS])


def _chains(dtype):
    X = _dense(dtype=dtype)
    return (fm.colSums(X), fm.colSums(fm.abs_(X)), fm.colMins(X),
            fm.colMaxs(X), fm.agg_col(X, "count_nonzero"))


# Each case: (engine outputs whose plan names the kernel, the kernel the
# engine must pick, kernel call given the unit, argument shapes and dtypes).
def _case_gram():
    X = _dense()
    return ((fm.crossprod(X),), "gram",
            lambda u: lambda x: gram_mod.gram(
                x, block_rows=min(u.block_rows, ROWS), interpret=False),
            [((ROWS, P), jnp.float32)])


def _case_xty():
    X, y = _dense(), _dense(p=1)
    return ((fm.crossprod(X, y),), "xty",
            lambda u: lambda x, y: gram_mod.xty(
                x, y, block_rows=min(u.block_rows, ROWS), interpret=False),
            [((ROWS, P), jnp.float32), ((ROWS, 1), jnp.float32)])


def _case_wgram():
    X, w = _dense(), _dense(p=1)
    return ((fm.crossprod(fm.mapply_col(X, w, "mul"), X),), "wgram",
            lambda u: lambda x, w: wg.wgram(
                x, w, block_rows=min(u.block_rows, ROWS), interpret=False),
            [((ROWS, P), jnp.float32), ((ROWS, 1), jnp.float32)])


def _case_chains(dtype, jdtype):
    def case():
        outs = _chains(dtype)
        return (outs, "fused_apply_agg",
                lambda u: lambda x: faa.fused_apply_agg(
                    x, u.chains, block_rows=min(u.block_rows, ROWS),
                    interpret=False),
                [((ROWS, P), jdtype)])
    return case


def _case_kmeans():
    X = _dense()
    centers = np.ones((K, P), np.float32)
    D = fm.inner_prod(X, centers.T, "squared_diff", "sum")
    labels = fm.which_min_row(D)
    outs = (fm.rowsum(X, labels, K), fm.table_(labels, K),
            fm.sum_(fm.rowMins(D)), labels)
    return (outs, "kmeans_assign",
            lambda u: lambda x, c: ka.kmeans_assign(
                x, c, block_rows=min(u.block_rows, ROWS), interpret=False),
            [((ROWS, P), jnp.float32), ((K, P), jnp.float32)])


# The sparse units hand the kernel no block_rows: it sizes its own.
_KMAX, _NCOL = len(LEVELS), sum(LEVELS)
_SLABS = [((ROWS, _KMAX), jnp.int32), ((ROWS, _KMAX), jnp.float32)]


def _case_spmm_gram():
    return ((fm.crossprod(_one_hot()),), "spmm_gram",
            lambda u: lambda c, v: spmm.spmm_gram(
                c, v, ncol=_NCOL, interpret=False),
            _SLABS)


def _case_spmm_xty():
    return ((fm.crossprod(_one_hot(), _dense(p=1)),), "spmm_xty",
            lambda u: lambda c, v, y: spmm.spmm_xty(
                c, v, y, ncol=_NCOL, interpret=False),
            _SLABS + [((ROWS, 1), jnp.float32)])


def _case_spmm_wgram():
    X, w = _one_hot(), _dense(p=1)
    return ((fm.crossprod(fm.mapply_col(X, w, "mul"), X),), "spmm_wgram",
            lambda u: lambda c, v, w: spmm.spmm_wgram(
                c, v, w, ncol=_NCOL, interpret=False),
            _SLABS + [((ROWS, 1), jnp.float32)])


CASES = {
    "gram": _case_gram,
    "xty": _case_xty,
    "wgram": _case_wgram,
    "fused_apply_agg-f32": _case_chains(np.float32, jnp.float32),
    "fused_apply_agg-i32": _case_chains(np.int32, jnp.int32),
    "kmeans_assign": _case_kmeans,
    "spmm_gram": _case_spmm_gram,
    "spmm_xty": _case_spmm_xty,
    "spmm_wgram": _case_spmm_wgram,
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    outs, kernel, make, shapes = CASES[case]()
    unit = _unit(*outs)
    assert unit.kernel == kernel
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(make(unit)).lower(*args).compile()
    # The kernel is a Mosaic custom call, not an interpreted loop.
    assert "tpu_custom_call" in compiled.as_text()
