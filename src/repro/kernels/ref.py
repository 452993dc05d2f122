"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def stencil2d5_ref(g: jax.Array) -> jax.Array:
    p = jnp.pad(g, 1)
    return 4.0 * g - p[:-2, 1:-1] - p[2:, 1:-1] - p[1:-1, :-2] - p[1:-1, 2:]


def stencil3d7_ref(g: jax.Array, eps_z: float = 1.0) -> jax.Array:
    p = jnp.pad(g, 1)
    ez = jnp.asarray(eps_z, g.dtype)
    return (
        (4.0 + 2.0 * ez) * g
        - p[:-2, 1:-1, 1:-1] - p[2:, 1:-1, 1:-1]
        - p[1:-1, :-2, 1:-1] - p[1:-1, 2:, 1:-1]
        - ez * p[1:-1, 1:-1, :-2] - ez * p[1:-1, 1:-1, 2:]
    )


def ell_spmv_ref(x: jax.Array, cols: jax.Array, vals: jax.Array) -> jax.Array:
    """Padded-row ELL SpMV: y[r] = sum_s vals[r,s] * x[cols[r,s]].
    Padded slots carry vals 0 (their gathered x value is irrelevant)."""
    return (vals * x[cols].astype(vals.dtype)).sum(axis=1)


def fused_dots_ref(mat: jax.Array, vec: jax.Array) -> jax.Array:
    return (mat.astype(jnp.float32) @ vec.astype(jnp.float32)).astype(mat.dtype)


def fused_axpy3_ref(zk1, zm1, zm2, c1, c2, scale):
    out = (
        zk1.astype(jnp.float32)
        + jnp.float32(c1) * zm1.astype(jnp.float32)
        + jnp.float32(c2) * zm2.astype(jnp.float32)
    ) * jnp.float32(scale)
    return out.astype(zk1.dtype)


def fused_iter_unfused(S, idx, scal, apply_a, prec, layout):
    """UNFUSED p(l)-CG vector phase — the memory-bound reference path the
    superkernel replaces (DESIGN.md §13): one separate jnp op per SPMV /
    preconditioner / fill copy / recurrence AXPY / solution update, each
    re-reading the (NV, N) slab.  Returns ``(S', mat, u_new)`` with the
    dot-block OPERANDS left unreduced so the caller issues the reduction
    through its backend (``ops.start``); :func:`fused_iter_ref` closes
    them into local partials for kernel-level comparison.

    This function is also the production unfused path of
    ``repro.core.pipelined_cg`` — solver-level fused/unfused parity
    reduces to kernel-level parity against THESE expressions, which the
    kernel mirrors term by term (tests/test_fused_iter.py).
    """
    from repro.kernels.fused_iter import idx_layout, scal_layout

    l = layout.l
    IX = idx_layout(l)
    IS = scal_layout(l)

    def get(row):
        return jax.lax.dynamic_index_in_dim(S, row, 0, keepdims=False)

    def put(out, row, vec):
        return jax.lax.dynamic_update_index_in_dim(out, vec, row, axis=0)

    # Sub-scopes of the vector phase, read only by the phase breakdown:
    # spmv (A and M^{-1}), recur (the u / z / K4 recurrences and their
    # stores), fill (pipeline-fill copies), dots (the dot block's
    # operands) and xp (the x / p update).
    late = idx[IX["f_late"]] != 0
    with jax.named_scope("spmv"):
        z_top = get(idx[IX["z_top"]])
    with jax.named_scope("recur"):
        u_i = get(idx[IX["u_i"]])
        u_im1 = get(idx[IX["u_im1"]])

    with jax.named_scope("spmv"):
        az = apply_a(z_top)
    with jax.named_scope("recur"):
        u_new0 = az - scal[IS["sig_i"]] * u_i
        u_new = jnp.where(
            late,
            (u_new0 - scal[IS["gam_new"]] * u_i
             - scal[IS["d2"]] * u_im1) / scal[IS["dlt_safe"]],
            u_new0)
    if layout.recurrence == "stable":
        # Coupled recurrence (arXiv:1902.03100, DESIGN.md §18): recompute
        # the top basis vector as M^{-1} u_{i+1} from the recurred u
        # instead of recurring z independently.  Early iterations are
        # bitwise-unchanged (u_new == u_new0 there).
        with jax.named_scope("spmv"):
            z_new = prec(u_new)
        z_fill = z_new
    else:
        with jax.named_scope("spmv"):
            z_new0 = prec(u_new0)
        with jax.named_scope("recur"):
            zl_im1 = get(idx[IX["zl_im1"]])
            z_new = jnp.where(
                late,
                (z_new0 - scal[IS["gam_new"]] * z_top
                 - scal[IS["d2"]] * zl_im1) / scal[IS["dlt_safe"]],
                z_new0)
        z_fill = z_new0

    out = S
    with jax.named_scope("fill"):
        for k in range(l):
            row = idx[IX["fill"] + k]
            fill_k = idx[IX["f_fill"] + k] != 0
            out = put(out, row, jnp.where(fill_k, z_fill, get(row)))

    recs = []
    with jax.named_scope("recur"):
        for k in range(l):
            zk1 = get(idx[IX["rec_a"] + k])
            zm1 = get(idx[IX["rec_b"] + k])
            zm2 = get(idx[IX["rec_c"] + k])
            rec = (zk1 + scal[IS["c1"] + k] * zm1
                   - scal[IS["d2"]] * zm2) / scal[IS["dlt_safe"]]
            val = jnp.where(late, rec, get(idx[IX["rec_w"] + k]))
            recs.append(val)
            out = put(out, idx[IX["rec_w"] + k], val)

        out = put(out, idx[IX["z_w"]], z_new)
        out = put(out, idx[IX["u_w"]], u_new)

    with jax.named_scope("dots"):
        rows = [get(idx[IX["mat_v"] + t]) for t in range(l)] + [recs[0]]
        rows += [get(idx[IX["mat_z"] + t]) for t in range(l - 1)] + [z_new]
        mat = jnp.stack(rows)

    with jax.named_scope("xp"):
        x_old = S[layout.x_row]
        p_old = S[layout.p_row]
        p_first = S[0] / scal[IS["eta0_safe"]]
        p_new = (get(idx[IX["p_im"]])
                 - scal[IS["d_prev"]] * p_old) / scal[IS["eta_new_safe"]]
        x_new = x_old + scal[IS["zet_prev"]] * p_old
        do_upd = idx[IX["f_upd"]] != 0
        is_first = idx[IX["f_first"]] != 0
        out = out.at[layout.x_row].set(jnp.where(do_upd, x_new, x_old))
        out = out.at[layout.p_row].set(
            jnp.where(is_first, p_first, jnp.where(do_upd, p_new, p_old)))
    return out, mat, u_new


def fused_iter_ref(S, idx, scal, apply_a, prec, layout):
    """Unfused oracle with the local dot partials closed: the allclose /
    bitwise reference for ``kernels.fused_iter.build_fused_iteration``.
    The partials go through THE dot-block row reduction
    (``repro.core.types.dot_block_rows``) — a matmul would round
    differently at the ULP level."""
    from repro.core.types import dot_block_rows

    out, mat, u_new = fused_iter_unfused(S, idx, scal, apply_a, prec, layout)
    return out, dot_block_rows(mat, u_new)


def decode_attention_ref(q, k, v, kv_len):
    """q (B,Hkv,G,D), k/v (B,Hkv,S,D), kv_len scalar int -> (B,Hkv,G,D) f32.

    Normalized output (the oracle for o_unnorm / l)."""
    b, hkv, g, d = q.shape
    s = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum(
        "bhgd,bhsd->bhgs", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    mask = jnp.arange(s)[None, None, None, :] < kv_len
    scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhgs,bhsd->bhgd", w, v.astype(jnp.float32))
