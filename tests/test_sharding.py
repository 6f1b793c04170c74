"""Unit tests for the mesh/PartitionSpec plumbing (sharding/partition.py,
launch/mesh.py): spec construction from the name-based rule tables, the
divisibility-fitting fallback, the paged-pool TP specs, and the TP mesh
constructor.  All of it runs on a single device (specs are pure data; the
1-device mesh degenerately satisfies every divisibility check); the fake
mesh stands in where a >1 axis size is needed so the fitting logic is
tested without device simulation.
"""
import types

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import mesh as M
from repro.sharding import partition as Pt


def _fake_mesh(**axes):
    """Shape-only stand-in for _fit_spec (which reads mesh.shape[axis])."""
    return types.SimpleNamespace(shape=dict(axes),
                                 axis_names=tuple(axes))


# --- _fit_spec ------------------------------------------------------------


def test_fit_spec_keeps_divisible_axes():
    mesh = _fake_mesh(data=2, model=4)
    sp = Pt._fit_spec(P(None, "model", None), (3, 8, 5), mesh)
    assert sp == P(None, "model", None)


def test_fit_spec_drops_indivisible_axis():
    mesh = _fake_mesh(data=2, model=4)
    # 6 % 4 != 0 -> the model axis is dropped, the rest survives
    sp = Pt._fit_spec(P("data", "model"), (4, 6), mesh)
    assert sp == P("data", None)


def test_fit_spec_trims_to_rank():
    mesh = _fake_mesh(model=2)
    sp = Pt._fit_spec(P(None, "model", None), (4, 4), mesh)
    assert len(sp) == 2


# --- rule tables ----------------------------------------------------------


def _dev_mesh():
    return M.make_tp_mesh(1)  # 1-device ('model',) mesh, always available


def test_serve_rules_spec_lookup():
    rules = Pt._serve_rules("data")
    assert Pt._spec_for("blocks/slot0/wq/w", rules, 3) == \
        P(None, None, "model")
    assert Pt._spec_for("blocks/slot0/wo/w", rules, 3) == \
        P(None, "model", None)
    assert Pt._spec_for("lm_head/w", rules, 2) == P(None, "model")
    # unmatched paths replicate
    assert Pt._spec_for("blocks/slot0/attn_q/M_idx", rules, 0) == P()


def test_make_param_shardings_on_struct_tree():
    mesh = _dev_mesh()
    tree = {"lm_head": {"w": jax.ShapeDtypeStruct((8, 16), np.int8)},
            "blocks": {"slot0": {"wq": {
                "w": jax.ShapeDtypeStruct((2, 4, 8), np.int8)}}}}
    sh = Pt.make_param_shardings(mesh, tree, mode="serve")
    assert sh["lm_head"]["w"].spec == P(None, "model")
    assert sh["blocks"]["slot0"]["wq"]["w"].spec == P(None, None, "model")


# --- paged-pool TP specs --------------------------------------------------


def test_kv_pool_pspec_shards_only_heads():
    sp = Pt.kv_pool_pspec()
    # (n_reps, n_pages, Hkv, P, hd): pages MUST stay unsharded — global
    # page ids are what keep the host allocator a single authority
    assert sp == P(None, None, "model", None, None)
    assert sp[1] is None and sp[2] == "model"


def test_paged_pool_shardings_tree():
    mesh = _dev_mesh()
    pool = {"slot0": {"k": jax.ShapeDtypeStruct((2, 9, 4, 4, 32), np.int8),
                      "v": jax.ShapeDtypeStruct((2, 9, 4, 4, 32), np.int8)}}
    sh = Pt.paged_pool_shardings(mesh, pool)
    for leaf in (sh["slot0"]["k"], sh["slot0"]["v"]):
        assert leaf.spec == P(None, None, "model", None, None)
        assert leaf.mesh.shape["model"] == 1


def test_paged_pool_shardings_drops_indivisible_heads():
    # Hkv=3 on a 4-way model axis cannot shard: _fit_spec falls back to
    # replicated rather than erroring (the engine asserts divisibility
    # before ever building such a pool)
    mesh = _fake_mesh(model=4)
    sp = Pt._fit_spec(Pt.kv_pool_pspec(), (2, 9, 3, 4, 32), mesh)
    assert sp == P(None, None, None, None, None)


# --- meshes ---------------------------------------------------------------


def test_make_tp_mesh_shape_and_axis():
    mesh = M.make_tp_mesh(1)
    assert mesh.axis_names == ("model",)
    assert mesh.shape["model"] == 1


def test_make_tp_mesh_rejects_oversubscription():
    with pytest.raises(AssertionError, match="devices"):
        M.make_tp_mesh(len(jax.devices()) + 1)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_make_tp_mesh_multi_device():
    mesh = M.make_tp_mesh(4)
    assert mesh.shape["model"] == 4
    assert len(set(mesh.devices.flat)) == 4


def test_shard_map_compat_runs_degenerate():
    """The TP engine wraps its forwards in ``jax.shard_map`` with the
    replication check off; that call form must run on a 1-device mesh."""
    mesh = M.make_tp_mesh(1)
    f = jax.shard_map(lambda x: x * 2, mesh=mesh, in_specs=(P(),),
                      out_specs=P(), check_vma=False)
    y = jax.jit(f)(np.arange(4, dtype=np.int32))
    assert np.array_equal(np.asarray(y), np.arange(4) * 2)
