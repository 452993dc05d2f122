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


def fused_iter_unfused(S, idx, scal, apply_a, prec, layout, partials):
    """UNFUSED p(l)-CG vector phase — the memory-bound reference path the
    superkernel replaces (DESIGN.md §13): one separate jnp op per SPMV /
    preconditioner / fill copy / recurrence AXPY / solution update, each
    re-reading slab rows.  Returns ``(S', partials(mat, u_new))``: the
    dot block reduced to this device's partials (``ops.partials``), for
    the caller to issue through its backend (``ops.start_partials``),
    as the superkernel's partials are.

    Rows are ``layout.row_shape``: (R, 128) tiles on the solver's unfused
    path, flat (N,) against the superkernel.  ``apply_a`` and ``prec``
    map rows to rows (``SlabLayout.row_map`` / ``row_scale``); every
    other op is elementwise on whole rows.  The dot block's operands are
    the rows' flat views (``layout.from_row``): the flat (K, n) block
    every substrate's ``dot_block`` reduces, whatever the storage.

    This function is also the production unfused path of
    ``repro.core.pipelined_cg`` — solver-level fused/unfused parity
    reduces to kernel-level parity against THESE expressions, which the
    kernel mirrors term by term (tests/test_fused_iter.py).
    """
    from repro.kernels.fused_iter import idx_layout, scal_layout

    l = layout.l
    IX = idx_layout(l)
    IS = scal_layout(l)

    # Every store updates the slab in place.  For that, no op may read a
    # version of the slab older than the one it stores into, or XLA
    # copies the whole slab first.  So every value that reads the
    # pre-iteration slab — the SPMV, the recurrences, the fills and the
    # dot block — is made before one barrier that the slab passes
    # through, and the x / p update reads the slab as stored so far.
    # It reads no row stored this iteration except into a value a
    # ``where`` discards (the ring windows are disjoint), so every value
    # equals the superkernel's, which reads before its first write.
    out = S

    def get(row):
        return jax.lax.dynamic_index_in_dim(out, row, 0, keepdims=False)

    def put(row, vec):
        nonlocal out
        out = jax.lax.dynamic_update_index_in_dim(out, vec, row, axis=0)

    # Sub-scopes of the vector phase, read only by the phase breakdown:
    # spmv (A and M^{-1}), recur (the u / z / K4 recurrences and their
    # stores), fill (pipeline-fill copies), dots (the dot block) and xp
    # (the x / p update).
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

    with jax.named_scope("fill"):
        fills = [jnp.where(idx[IX["f_fill"] + k] != 0, z_fill,
                           get(idx[IX["fill"] + k])) for k in range(l)]

    recs = []
    with jax.named_scope("recur"):
        for k in range(l):
            zk1 = get(idx[IX["rec_a"] + k])
            zm1 = get(idx[IX["rec_b"] + k])
            zm2 = get(idx[IX["rec_c"] + k])
            rec = (zk1 + scal[IS["c1"] + k] * zm1
                   - scal[IS["d2"]] * zm2) / scal[IS["dlt_safe"]]
            recs.append(jnp.where(late, rec, get(idx[IX["rec_w"] + k])))

    # Rows i-2l+1..i+1 of G column i+1: the ZK^(0) V-range (last entry
    # freshly recurred), the ZK^(l) Z-range, and z_{i+1} itself.
    with jax.named_scope("dots"):
        rows = [get(idx[IX["mat_v"] + t]) for t in range(l)] + [recs[0]]
        rows += [get(idx[IX["mat_z"] + t]) for t in range(l - 1)] + [z_new]
        dots = partials(jnp.stack([layout.from_row(r) for r in rows]),
                        layout.from_row(u_new))

    out, fills, recs, z_new, u_new, dots = jax.lax.optimization_barrier(
        (out, fills, recs, z_new, u_new, dots))

    with jax.named_scope("fill"):
        for k in range(l):
            put(idx[IX["fill"] + k], fills[k])
    with jax.named_scope("recur"):
        for k in range(l):
            put(idx[IX["rec_w"] + k], recs[k])
        put(idx[IX["z_w"]], z_new)
        put(idx[IX["u_w"]], u_new)

    with jax.named_scope("xp"):
        do_upd = idx[IX["f_upd"]] != 0
        is_first = idx[IX["f_first"]] != 0
        x_old = get(layout.x_row)
        x_new = x_old + scal[IS["zet_prev"]] * get(layout.p_row)
        put(layout.x_row, jnp.where(do_upd, x_new, x_old))
        p_old = get(layout.p_row)
        p_first = get(0) / scal[IS["eta0_safe"]]
        p_new = (get(idx[IX["p_im"]])
                 - scal[IS["d_prev"]] * p_old) / scal[IS["eta_new_safe"]]
        put(layout.p_row, jnp.where(
            is_first, p_first, jnp.where(do_upd, p_new, p_old)))
    return out, dots


def fused_iter_ref(S, idx, scal, apply_a, prec, layout):
    """Unfused oracle with the local dot partials closed: the allclose /
    bitwise reference for ``kernels.fused_iter.build_fused_iteration``.
    The partials go through THE dot-block row reduction
    (``repro.core.types.dot_block_rows``) — a matmul would round
    differently at the ULP level."""
    from repro.core.types import dot_block_rows

    return fused_iter_unfused(S, idx, scal, apply_a, prec, layout,
                              dot_block_rows)


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
