"""Pallas lane-chunked cache pass for the wavefront engine.

One sequential grid sweep over the wave's L lanes, with the whole cache
state — tags/RRIP/meta rows, EAF bits + generation, PC-table counters
and the wave's classifier rows — resident in VMEM scratch between grid
steps (the [sets, ways] arrays are ~16KB each at paper scale, far under
the VMEM budget). Each grid step services one lane's [B] requests with
the exact per-lane math of ``ref.lane_cache_step`` applied to the
scratch-held state, writes the lane's record block, and the final step
flushes the advanced state to the outputs. Because a grid step consumes
the state exactly as the reference scan's lane sub-step does, parity
with the ref/fused backends is structural — pinned bitwise by
tests/test_kernels.py under ``interpret=True``.

Status: interpret mode only. The TPU compiler (Mosaic, jax 0.9.0)
refuses this kernel for a v5e, at every wave width tried (B = 8 and
512): the ``(1, B)`` lane block over the ``(lanes, B)`` arrays breaks
the rule that a block's last two dims be divisible by (8, 128) or equal
the array's. Past that, the in-kernel gathers (tag-row reads by set
index) would need a one-hot rewrite before Mosaic could lower them. So
the kernel is an explicit ``backend="pallas"`` opt-in, and ``"auto"``
resolves to ``"fused"``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import classifier as CLF
from repro.core.engine.state import SimParams, SimState
from repro.kernels.cache_pass import ref as _ref
from repro.policy import PolicyArrays

F32 = jnp.float32
I32 = jnp.int32

#: SimState fields the cache pass reads/writes (scratch-carried, in
#: order); the queue/metric fields are dead inside the pass and enter
#: the kernel as zeros.
_STATE_FIELDS = ("tags", "rrip", "meta_type", "eaf", "eaf_gen", "eaf_ctr",
                 "pc_hits", "pc_acc", "pc_req")
_N_STATE = len(_STATE_FIELDS)
_N_CLF = len(CLF.ClassifierState._fields)
_N_REC = 9


def _cache_kernel(*refs, lanes, n_pa, pa_treedef, pa_shapes, prm):
    """Grid step = one lane. ``refs`` layout (inputs, outputs, scratch):

    inputs:  addr [1, B] (lane-blocked) · t0, pc_b, owt_b, slot_ok,
             tokens_b [B] · clf rows ×6 · state ×9 · pa leaves ×n_pa
    outputs: state ×9 · clf rows ×6 · records ×9 ([1, B] lane-blocked)
    scratch: state ×9 · clf rows ×6 (VMEM)
    """
    n_in = 6 + _N_CLF + _N_STATE + n_pa
    n_out = _N_STATE + _N_CLF + _N_REC
    ins, outs, scratch = (refs[:n_in], refs[n_in:n_in + n_out],
                          refs[n_in + n_out:])
    (addr_ref, t0_ref, pc_ref, owt_ref, ok_ref, tok_ref) = ins[:6]
    clf_in = ins[6:6 + _N_CLF]
    st_in = ins[6 + _N_CLF:6 + _N_CLF + _N_STATE]
    pa_in = ins[6 + _N_CLF + _N_STATE:]
    st_out = outs[:_N_STATE]
    clf_out = outs[_N_STATE:_N_STATE + _N_CLF]
    rec_out = outs[_N_STATE + _N_CLF:]
    st_sc = scratch[:_N_STATE]
    clf_sc = scratch[_N_STATE:]

    k = pl.program_id(0)

    @pl.when(k == 0)
    def _load():
        for dst, src in zip(st_sc + clf_sc, st_in + clf_in):
            dst[...] = src[...]

    pa = jax.tree_util.tree_unflatten(
        pa_treedef,
        [r[...].reshape(s) for r, s in zip(pa_in, pa_shapes)])
    sv = dict(zip(_STATE_FIELDS, (r[...] for r in st_sc)))
    zb = jnp.zeros((1,), F32)
    zi = jnp.zeros((1,), I32)
    st = SimState(tags=sv["tags"], rrip=sv["rrip"],
                  meta_type=sv["meta_type"], bank_free=zb, cur_row=zi,
                  hp_free=zb, lp_free=zb, clf=None,
                  eaf=sv["eaf"], eaf_gen=sv["eaf_gen"][0],
                  eaf_ctr=sv["eaf_ctr"][0], pc_hits=sv["pc_hits"],
                  pc_acc=sv["pc_acc"], pc_req=sv["pc_req"],
                  tot_hits=zi, tot_acc=zi, metrics={})
    clf_b = CLF.ClassifierState(*(r[...] for r in clf_sc))

    addr = addr_ref[0, :]
    slot_ok = ok_ref[...]
    valid = (addr >= 0) & slot_ok
    t_arr = t0_ref[...] + k.astype(F32) * prm.lane_skew
    st, clf_b, rec = _ref.lane_cache_step(
        st, t_arr, addr, pc_ref[...], valid, owt_ref[...], prm, pa,
        clf_b, tok_ref[...])

    for dst, name in zip(st_sc, _STATE_FIELDS):
        v = getattr(st, name)
        dst[...] = v.reshape(dst.shape) if v.ndim == 0 else v
    for dst, v in zip(clf_sc, clf_b):
        dst[...] = v
    for dst, v in zip(rec_out, rec):
        dst[0, :] = v

    @pl.when(k == lanes - 1)
    def _flush():
        for dst, src in zip(st_out + clf_out, st_sc + clf_sc):
            dst[...] = src[...]


def wave_cache_kernel(st: SimState, clf_b0: CLF.ClassifierState, tokens_b,
                      t0, addr_lb, pc_b, owt_b, slot_ok, prm: SimParams,
                      pa: PolicyArrays, *, interpret: bool = False
                      ) -> tuple:
    """``ops.wave_cache_pass`` backend ``"pallas"``: same signature and
    return contract as ``ref.wave_cache_pass_ref``."""
    lanes, B = addr_lb.shape
    pa_leaves, pa_treedef = jax.tree_util.tree_flatten(pa)
    pa_shapes = tuple(x.shape for x in pa_leaves)
    st_vals = [jnp.atleast_1d(getattr(st, f)) for f in _STATE_FIELDS]

    whole = lambda x: pl.BlockSpec(x.shape, lambda i: (0,) * x.ndim)
    lane_spec = pl.BlockSpec((1, B), lambda i: (i, 0))

    in_arrays = ([addr_lb, t0, pc_b, owt_b, slot_ok, tokens_b]
                 + list(clf_b0) + st_vals
                 + [jnp.atleast_1d(x) for x in pa_leaves])
    in_specs = [lane_spec] + [whole(x) for x in in_arrays[1:]]

    out_shape = ([jax.ShapeDtypeStruct(x.shape, x.dtype) for x in st_vals]
                 + [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in clf_b0]
                 + [jax.ShapeDtypeStruct((lanes, B), d)
                    for d in (F32, I32, bool, bool, bool, bool, bool,
                              I32, bool)])
    out_specs = ([whole(x) for x in st_vals] + [whole(x) for x in clf_b0]
                 + [lane_spec] * _N_REC)

    scratch = ([pltpu.VMEM(x.shape, x.dtype) for x in st_vals]
               + [pltpu.VMEM(x.shape, x.dtype) for x in clf_b0])

    outs = pl.pallas_call(
        partial(_cache_kernel, lanes=lanes, n_pa=len(pa_leaves),
                pa_treedef=pa_treedef, pa_shapes=pa_shapes, prm=prm),
        grid=(lanes,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*in_arrays)

    st_new = outs[:_N_STATE]
    clf_new = outs[_N_STATE:_N_STATE + _N_CLF]
    recs = tuple(outs[_N_STATE + _N_CLF:])
    upd = {f: (v.reshape(getattr(st, f).shape)
               if getattr(st, f).ndim == 0 else v)
           for f, v in zip(_STATE_FIELDS, st_new)}
    return (st._replace(**upd), CLF.ClassifierState(*clf_new), recs)
