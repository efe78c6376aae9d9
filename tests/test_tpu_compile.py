"""The main path's Pallas kernels compile for a TPU v5e.

JAX's TPU compiler ships with jaxlib, so each test lowers one kernel with
``interpret=False`` at the TPC-H widths and compiles it for one chip of a
*described* ``v5e:2x2`` topology — no chip attached.  What Mosaic refuses
(an op it cannot lower, more VMEM than a kernel may use) fails here, where
interpret-mode tests cannot see it.

Only one process at a time may load the TPU library, so the topology is
described inside a fixture of this file and nowhere else (never at import,
in ``conftest.py`` or in ``parametrize``), and the compiles run in the
test's own process.
"""

from __future__ import annotations

import inspect
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.data import datagen
from repro.kernels import fused_ingest as fk
from repro.kernels import ops
from repro.kernels import query_intersect as qk
from repro.kernels import route_records as rk

D = 21  # TPC-H-like table width (datagen.tpch_like_schema)
B = 256  # its 146 categorical bits, padded to a LANE multiple
C = 256  # cut bucket of the TPC-H template workload's 212 candidate cuts
A = 2  # advanced (column-vs-column) cuts
M = 65_536  # records per ingest batch (chip_smoke.py's default)
TILE_M = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def f32(topo):
    """Shape factory for f32 operands placed on one described chip.

    The persistent compilation cache is off meanwhile: an entry compiled
    for a described chip cannot be read back without one.
    """
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=one_chip
    )
    jax.config.update("jax_enable_compilation_cache", was)


def _assert_mosaic_kernel(lowered) -> None:
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def _segment_operands(ca, seg, shaped) -> dict:
    """The ingest plan's cut operands at a cell's segment buckets ``seg``:
    the cut axis leads, except in the transposed IN masks."""
    assert set(ca) == set(seg)
    return {
        k: shaped((a.shape[0], seg[k]) if k == "mask_iT"
                  else (seg[k],) + a.shape[1:], a.dtype)
        for k, a in ca.items()
    }


def _assert_in_lookup_is_a_product(hlo: str) -> None:
    """No gather of the compiled step carries ``step.in_lookup`` in its
    ``op_name``; a dot or convolution does."""
    from repro import spans

    ops = set()
    for line in hlo.splitlines():
        if spans.STEP_IN_LOOKUP in line and " = " in line:
            rhs = line.split(" = ", 1)[1]
            ops.add(re.search(r"\s([a-z][a-z0-9-]*)\(", rhs).group(1))
    assert "gather" not in ops, ops
    assert ops & {"dot", "convolution"}, ops


@pytest.mark.parametrize("leaf_bucket", [128, 1024, fk.MAX_LEAF_BUCKET])
def test_fused_ingest_compiles(f32, leaf_bucket):
    _assert_mosaic_kernel(fk.fused_ingest_pallas.lower(
        f32(M, D), f32(M, 1), f32(D, C), f32(1, C), f32(B, C), f32(1, D),
        f32(1, D), f32(A, 3), f32(A, C), f32(1, C),
        f32(C, leaf_bucket), f32(C, leaf_bucket), f32(1, leaf_bucket),
        tile_m=TILE_M, tile_l=128, n_cat_bits=B, n_adv=A, interpret=False,
    ))


def test_eval_cuts_compiles(f32):
    _assert_mosaic_kernel(rk.eval_cuts_pallas.lower(
        f32(M, D), f32(D, C), f32(1, C), f32(B, C), f32(1, D), f32(1, D),
        f32(A, 3), f32(A, C), f32(1, C),
        tile_m=TILE_M, n_cat_bits=B, n_adv=A, interpret=False,
    ))


def test_locate_leaf_compiles(f32):
    _assert_mosaic_kernel(rk.locate_leaf_pallas.lower(
        f32(M, C), f32(C, 1024), f32(C, 1024), f32(1, 1024),
        tile_m=TILE_M, tile_l=128, interpret=False,
    ))


def test_query_intersect_compiles_at_default_tiles(f32):
    """The tiles ``ops.query_intersect`` uses unless told otherwise."""
    params = inspect.signature(ops.query_intersect).parameters
    tile_l, tile_c = params["tile_l"].default, params["tile_c"].default
    schema = datagen.tpch_like_schema()
    off = schema.cat_offsets
    bits = schema.total_cat_bits
    n_leaf, n_conj = 1024, 128
    _assert_mosaic_kernel(qk.query_intersect_pallas.lower(
        f32(n_leaf, D), f32(n_leaf, D), f32(n_leaf, bits), f32(n_leaf, A),
        f32(n_leaf, A), f32(1, n_leaf),
        f32(D, n_conj), f32(D, n_conj), f32(bits, n_conj), f32(A, n_conj),
        f32(A, n_conj),
        tile_l=tile_l,
        tile_c=tile_c,
        numeric_dims=tuple(
            int(d) for d in np.nonzero(~schema.is_categorical)[0]
        ),
        cat_segments=tuple(
            (int(off[d]), int(off[d]) + schema.columns[d].dom)
            for d in np.nonzero(schema.is_categorical)[0]
        ),
        n_adv=A,
        interpret=False,
    ))


def test_jax_ingest_step_compiles(f32):
    """The default backend's fused ingest step at the tpch_sf1 cell's
    geometry: 65,536 rows x 21 columns, leaf bucket 1024, grouped cut
    bucket 64 range + 32 IN + 4 advanced.  Rows are located by the
    path-matrix product, so the compiled step has no descent loop, and
    the IN cuts are a product with the one-hot, not a gather."""
    from repro.core.predicates import OP_LT, CutTableBuilder
    from repro.core.qdtree import singleton_tree
    from repro.engine import backends
    from repro.engine.plan import PlanCache

    schema = datagen.tpch_like_schema()
    b = CutTableBuilder(schema)
    b.add_adv(1, OP_LT, 2)  # l_commitdate < l_receiptdate
    b.add_adv(0, OP_LT, 1)  # l_shipdate < l_commitdate
    tree = singleton_tree(schema, b.build(), np.arange(1)).freeze()
    plan = backends.get_backend("jax")._ingest_plan(tree, PlanCache())
    assert plan.meta["locate"] == "paths"
    assert plan.meta["in_lookup"] == "product"
    one_chip = f32(1).sharding
    seg = {"dim_r": 64, "cut_r": 64, "mask_iT": 32, "advsel": 4, "adv": A}

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    kw = dict(plan.fn.keywords)
    kw.update(
        loc={"path_diff": f32(100, 1024), "path_len": f32(1024)},
        ca=_segment_operands(kw["ca"], seg, shaped),
        l_dump=1025, depth=32,
    )
    compiled = plan.fn.func.lower(
        shaped((M, D), jnp.int32), shaped((M,), jnp.bool_), **kw
    ).compile()
    assert " while(" not in compiled.as_text()
    _assert_in_lookup_is_a_product(compiled.as_text())


# categorical widths of the tpcds_sf1 benchmark table (TPC-DS store_sales
# joined to its dimensions): 24 columns, 2,241 bits, beside 11 numeric
STAR_CAT_DOMS = (5, 12, 7, 60, 24, 10, 100, 712, 1000, 100, 12, 51, 30, 30,
                 2, 2, 5, 7, 6, 10, 51, 1, 2, 2)


def test_jax_ingest_step_compiles_at_star_width(f32):
    """The same step at the tpcds_sf1 cell's geometry: 65,536 rows x 35
    columns with 2,241 categorical bits, leaf bucket 1024, grouped cut
    bucket 32 range + 64 IN + 4.  The plan keeps the path-matrix product
    and the one-hot stats product, evaluates the IN cuts by the one-hot
    product, and the step fits one chip."""
    from repro.core.predicates import Column, CutTableBuilder, Schema
    from repro.core.qdtree import singleton_tree
    from repro.engine import backends
    from repro.engine.plan import PlanCache

    cols = [Column(f"n{i}", "numeric", 1000) for i in range(11)]
    cols += [Column(f"c{i}", "categorical", d)
             for i, d in enumerate(STAR_CAT_DOMS)]
    schema = Schema(tuple(cols))
    assert schema.total_cat_bits == 2241
    tree = singleton_tree(schema, CutTableBuilder(schema).build(),
                          np.arange(1)).freeze()
    plan = backends.get_backend("jax")._ingest_plan(tree, PlanCache())
    assert plan.meta["cat_gemm"] and plan.meta["cat_bits"] == 2241
    assert plan.meta["in_lookup"] == "product"
    one_chip = f32(1).sharding
    seg = {"dim_r": 32, "cut_r": 32, "mask_iT": 64, "advsel": 4, "adv": 1}

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    kw = dict(plan.fn.keywords)
    kw.update(
        loc={"path_diff": f32(100, 1024), "path_len": f32(1024)},
        ca=_segment_operands(kw["ca"], seg, shaped),
        l_dump=1025, depth=32, locate="paths",
    )
    compiled = plan.fn.func.lower(
        shaped((M, len(cols)), jnp.int32), shaped((M,), jnp.bool_), **kw
    ).compile()
    assert " while(" not in compiled.as_text()
    _assert_in_lookup_is_a_product(compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
