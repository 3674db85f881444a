"""Expert parallelism: GShard/Switch-style MoE dispatch over an ``ep``
mesh axis.

SURVEY §2.3 EP row (absent in the reference): "all-to-all token dispatch
over ICI mesh axis (XLA all_to_all)".  Design:

- tokens are sharded over ``ep`` (each device routes its own T/ep tokens),
- stacked expert weights are sharded over ``ep`` (each device OWNS E/ep
  experts — true expert memory scaling),
- each device builds a capacity-limited dispatch tensor for ALL experts
  from its local tokens, then one ``lax.all_to_all`` moves every token to
  its expert's device, the local experts run as one batched einsum on the
  MXU, and a second ``all_to_all`` brings outputs home for the top-k
  combine.

The eager dense-gather reference is ``gluon.nn.MoE.forward``; with a
sufficient ``capacity_factor`` the two are numerically identical (pinned
by tests/python/unittest/test_parallel.py).
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError
from ..ndarray.ndarray import NDArray

__all__ = ["moe_apply"]


from ._smap import shard_map_compat


def _build_dispatch(probs, k, C):
    """Capacity-limited top-k dispatch/combine tensors (Switch transformer
    routing).  probs: (T, E) -> dispatch (T, E, C) 0/1, combine (T, E, C)
    weights, aux load-balancing terms."""
    T, E = probs.shape
    top_vals, top_idx = lax.top_k(probs, k)
    norm = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)
    dispatch = jnp.zeros((T, E, C), probs.dtype)
    combine = jnp.zeros((T, E, C), probs.dtype)
    counts = jnp.zeros((E,), probs.dtype)
    for s in range(k):  # k is small and static
        oh = jax.nn.one_hot(top_idx[:, s], E, dtype=probs.dtype)
        pos = counts[None, :] + jnp.cumsum(oh, 0) - oh
        pos_tok = (pos * oh).sum(-1)
        sel = oh * (pos_tok < C)[:, None]
        slot = jax.nn.one_hot(pos_tok.astype(jnp.int32), C,
                              dtype=probs.dtype)
        dispatch = dispatch + sel[:, :, None] * slot[:, None, :]
        combine = combine + (sel * norm[:, s:s + 1])[:, :, None] * \
            slot[:, None, :]
        counts = counts + sel.sum(0)
    # Switch aux loss terms: fraction routed (first choice) x mean prob
    f_e = jax.nn.one_hot(top_idx[:, 0], E, dtype=probs.dtype).sum(0)
    p_e = probs.sum(0)
    return dispatch, combine, f_e, p_e


def moe_apply(moe, x, mesh=None, axis_name="ep", capacity_factor=2.0,
              return_aux=False):
    """Expert-parallel application of a ``gluon.nn.MoE`` block.

    x: (T, d) tokens (NDArray or jax array), T divisible by the ep axis
    size.  Returns the combined (T, units) output (and the scalar
    load-balancing aux loss when ``return_aux``).
    """
    if mesh is None or axis_name not in mesh.axis_names:
        raise MXNetError("moe_apply needs a mesh with a %r axis"
                         % (axis_name,))
    ep = int(mesh.shape[axis_name])
    E, k = moe._E, moe._k
    if moe._gated or moe.b1 is None or moe._count != E \
            or moe._score != "softmax" or moe._scale != 1.0:
        raise MXNetError("moe_apply takes the ungated, biased MoE with "
                         "every expert held, a softmax router and no "
                         "routing scale")
    if E % ep:
        raise MXNetError("num_experts %d not divisible by ep=%d" % (E, ep))
    xv = x._data if isinstance(x, NDArray) else jnp.asarray(x)
    lead = xv.shape[:-1]
    xv = xv.reshape(-1, xv.shape[-1])
    T = xv.shape[0]
    if T % ep:
        raise MXNetError("token count %d not divisible by ep=%d" % (T, ep))
    T_loc = T // ep
    E_loc = E // ep
    C = max(1, int(_np.ceil(k * T_loc / E * capacity_factor)))

    params = {"w1": moe.w1.data()._data, "b1": moe.b1.data()._data,
              "w2": moe.w2.data()._data, "b2": moe.b2.data()._data,
              "gate": moe.gate.data()._data}
    act = moe._activation

    def local_fn(w1, b1, w2, b2, gate, xl):
        # xl: (T_loc, d) this device's tokens; w*/b*: this device's experts
        logits = jnp.einsum("td,ed->te", xl, gate)
        probs = jax.nn.softmax(logits, axis=-1)
        dispatch, combine, f_e, p_e = _build_dispatch(probs, k, C)
        xe = jnp.einsum("tec,td->ecd", dispatch, xl)       # (E, C, d)
        # all_to_all #1: tokens travel to their expert's device
        xe = lax.all_to_all(xe, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)                    # (ep*E_loc,C,d)
        xe = xe.reshape(ep, E_loc, C, xe.shape[-1])        # src-major
        h = act(jnp, jnp.einsum("secd,edh->sech", xe, w1) +
                b1[None, :, None])
        ye = jnp.einsum("sech,ehu->secu", h, w2) + b2[None, :, None]
        # all_to_all #2: expert outputs travel home
        ye = ye.reshape(ep * E_loc, C, ye.shape[-1])
        ye = lax.all_to_all(ye, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)                    # (E, C, u)
        y = jnp.einsum("tec,ecu->tu", combine, ye)
        # global load-balance loss: E * sum_e mean_frac_e * mean_prob_e
        f_tot = lax.psum(f_e, axis_name)
        p_tot = lax.psum(p_e, axis_name)
        aux = E * jnp.sum((f_tot / T) * (p_tot / T))
        return y, aux

    pspec = {"w1": P(axis_name), "b1": P(axis_name),
             "w2": P(axis_name), "b2": P(axis_name), "gate": P()}
    psh = {n: NamedSharding(mesh, s) for n, s in pspec.items()}
    # cache the sharded weights keyed on the source buffers: repeated
    # moe_apply calls with unchanged weights must not re-scatter the full
    # expert stack over ICI every step (a new param array — new id —
    # invalidates the entry)
    pkey = (id(mesh), tuple(sorted((n, id(v)) for n, v in params.items())))
    cached = getattr(moe, "_ep_param_cache", None)
    if cached is None or cached[0] != pkey:
        sharded = {n: jax.device_put(v, psh[n]) for n, v in params.items()}
        moe._ep_param_cache = cached = (pkey, sharded)
    params = cached[1]
    xv = jax.device_put(xv, NamedSharding(mesh, P(axis_name)))
    # compile once per (mesh, shapes, capacity) and cache on the block —
    # jit's own cache is keyed on function identity, so a fresh lambda per
    # call would re-trace + re-compile every step
    cache = getattr(moe, "_ep_cache", None)
    if cache is None:
        cache = moe._ep_cache = {}
    key = (id(mesh), axis_name, xv.shape, str(xv.dtype), C, k)
    fn = cache.get(key)
    if fn is None:
        smap = shard_map_compat(
            lambda pr, xl: local_fn(pr["w1"], pr["b1"], pr["w2"], pr["b2"],
                                    pr["gate"], xl),
            mesh=mesh, in_specs=(pspec, P(axis_name)),
            out_specs=(P(axis_name), P()))
        fn = cache[key] = jax.jit(smap)
    with mesh:
        y, aux = fn(params, xv)
    y = y.reshape(lead + (y.shape[-1],))
    if return_aux:
        return NDArray(y), NDArray(aux)
    return NDArray(y)
