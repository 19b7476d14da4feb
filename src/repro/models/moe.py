"""Mixture-of-Experts layer: top-k routing with sort-based dispatch.

Capacity-bounded, drop-on-overflow dispatch implemented with argsort +
scatter (static shapes throughout — XLA/GSPMD friendly, no [T,E,C]
one-hot dispatch tensors).  Expert weights carry a leading E dim that is
expert-parallel-sharded on the 'model' mesh axis when E divides the axis
(qwen3-moe: 128 experts / 16 = 8 per device); otherwise tensor-parallel
inside each expert (mixtral: 8 experts, d_ff sharded).

Aux load-balancing loss follows Switch/Mixtral: E · Σ_e f_e · p_e.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def init_moe(key, l: int, d_model: int, d_ff: int, n_experts: int,
             dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    scale_in = 1.0 / jnp.sqrt(jnp.asarray(d_model, jnp.float32))
    scale_out = 1.0 / jnp.sqrt(jnp.asarray(d_ff, jnp.float32))
    return {
        "router": jax.random.normal(ks[0], (l, d_model, n_experts), dtype) * scale_in,
        "wi": jax.random.normal(ks[1], (l, n_experts, d_model, d_ff), dtype) * scale_in,
        "wg": jax.random.normal(ks[2], (l, n_experts, d_model, d_ff), dtype) * scale_in,
        "wo": jax.random.normal(ks[3], (l, n_experts, d_ff, d_model), dtype) * scale_out,
    }


def moe_apply(x: jnp.ndarray, router: jnp.ndarray, wi: jnp.ndarray,
              wg: jnp.ndarray, wo: jnp.ndarray, *, top_k: int,
              capacity_factor: float = 1.25,
              ep_axis: str | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, D] -> (y [B, S, D], aux_loss scalar).

    Dispatch: flatten (token, choice) pairs, argsort by expert id,
    compute each pair's slot within its expert's capacity-padded buffer,
    scatter, run batched expert matmuls [E,C,D]×[E,D,F], gather back.
    Overflow pairs land in a trash slot and contribute zero.
    """
    b, s, d = x.shape
    e = router.shape[-1]
    t = b * s
    xf = x.reshape(t, d)

    gates = jax.nn.softmax(
        (xf.astype(jnp.float32) @ router.astype(jnp.float32)), axis=-1)  # [T,E]
    weights, expert_idx = jax.lax.top_k(gates, top_k)                    # [T,k]
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    # Aux load-balancing loss (computed before any dropping).
    frac_tokens = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (t * top_k))
    frac_probs = gates.mean(0)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    capacity = int(max(top_k, capacity_factor * t * top_k / e))

    sel = expert_idx.reshape(-1)                       # [S_all = T*k]
    order = jnp.argsort(sel)                           # stable
    sel_sorted = sel[order]
    token_sorted = order // top_k
    # Position of each pair within its expert's run.
    run_start = jnp.searchsorted(sel_sorted, jnp.arange(e), side="left")
    pos_in_run = jnp.arange(t * top_k) - run_start[sel_sorted]
    keep = pos_in_run < capacity
    slot = jnp.where(keep, sel_sorted * capacity + pos_in_run,
                     e * capacity)                     # trash slot

    buf = jnp.zeros((e * capacity + 1, d), x.dtype)
    buf = buf.at[slot].set(xf[token_sorted])
    xe = buf[: e * capacity].reshape(e, capacity, d)
    if ep_axis:  # expert-parallel dispatch boundary (GSPMD all-to-all)
        from jax.sharding import PartitionSpec as P
        xe = jax.lax.with_sharding_constraint(xe, P(ep_axis, None, None))

    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
         * jnp.einsum("ecd,edf->ecf", xe, wi))
    ye = jnp.einsum("ecf,efd->ecd", h, wo)             # [E,C,D]
    if ep_axis:
        from jax.sharding import PartitionSpec as P
        ye = jax.lax.with_sharding_constraint(ye, P(ep_axis, None, None))

    yf = ye.reshape(e * capacity, d)
    y_pairs = jnp.where(keep[:, None], yf[jnp.minimum(slot, e * capacity - 1)],
                        0.0)                           # [T*k, D] sorted order
    w_pairs = weights.reshape(-1)[order]
    contrib = y_pairs * w_pairs[:, None].astype(y_pairs.dtype)
    y = jnp.zeros((t, d), x.dtype).at[token_sorted].add(contrib)
    return y.reshape(b, s, d), aux


# ----------------------------------------------------------------------
# Manual (shard_map) dispatch — §Perf I1
# ----------------------------------------------------------------------
# The GSPMD path above lets XLA partition a *global* argsort and
# global-capacity buffers — at 1M tokens × 94 layers that lowers to
# thousands of seconds of collectives (see EXPERIMENTS.md baseline).
# But MoE routing is embarrassingly parallel over the batch: activations
# are sharded over the DP axes and REPLICATED over 'model', while
# experts are sharded over 'model'.  So every device can route its local
# tokens to its local experts with ZERO dispatch communication; the only
# collective left is the same psum a dense TP MLP needs, plus the
# explicit FSDP all-gather of the expert weights.


def _dispatch_local(xf, expert_idx, weights, e0: int, e_loc: int,
                    capacity: int):
    """Local-token → local-expert dispatch (no collectives).

    xf: [T, D]; expert_idx/weights: [T, k] global expert ids + gates.
    Selects pairs with e0 <= id < e0+e_loc, packs them into
    [e_loc, capacity, D].  Returns (xe, slot, keep, token_sorted,
    w_sorted) for the combine step.
    """
    t, d = xf.shape
    k = expert_idx.shape[1]
    sel = expert_idx.reshape(-1) - e0                  # [T*k]
    mine = (sel >= 0) & (sel < e_loc)
    sel_c = jnp.where(mine, sel, e_loc)                # foreign -> sentinel
    order = jnp.argsort(sel_c)
    # §Perf I1b: sorted order puts LOCAL experts first — only the head of
    # the sorted pair list can land in the capacity buffers.  Slicing to
    # 2·e_loc·capacity statically shrinks every [T·k, D] dispatch gather
    # ~(E/e_loc)/2× (6.4× for qwen3-moe EP=16).  The 2× slack absorbs
    # early-expert overflow; beyond that, pairs drop exactly as capacity
    # overflow does.  TP-F (e_loc=E) keeps the full list.
    q = min(t * k, 2 * e_loc * capacity)
    order_q = order[:q]
    sel_sorted = sel_c[order_q]
    token_sorted = order_q // k
    run_start = jnp.searchsorted(sel_sorted, jnp.arange(e_loc), side="left")
    pos_in_run = jnp.arange(q) - run_start[jnp.minimum(sel_sorted,
                                                       e_loc - 1)]
    keep = (sel_sorted < e_loc) & (pos_in_run < capacity)
    slot = jnp.where(keep, sel_sorted * capacity + pos_in_run,
                     e_loc * capacity)
    buf = jnp.zeros((e_loc * capacity + 1, d), xf.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], xf[token_sorted], 0))
    xe = buf[: e_loc * capacity].reshape(e_loc, capacity, d)
    w_sorted = weights.reshape(-1)[order_q].astype(xf.dtype)
    return xe, slot, keep, token_sorted, w_sorted


def make_sharded_moe(mesh, *, top_k: int, capacity_factor: float,
                     n_experts: int, dp_axes: tuple):
    """Returns moe(x, router, wi, wg, wo) -> (y, aux) using manual
    collectives.  Expert placement follows sharding/specs.py: experts on
    'model' when divisible (EP), else d_ff on 'model' (TP-F)."""
    from jax.sharding import PartitionSpec as P

    model_size = mesh.shape["model"]
    ep = n_experts % model_size == 0
    dp = tuple(dp_axes)

    def body(x_loc, router, wi_loc, wg_loc, wo_loc):
        # local shapes: x [B_loc, S, D]; router [D, E] replicated;
        # EP:  wi [E_loc, D/fsdp, F]  TP-F: wi [E, D/fsdp, F_loc]
        b_loc, s, d = x_loc.shape
        wi_f = lax.all_gather(wi_loc, "data", axis=1, tiled=True)
        wg_f = lax.all_gather(wg_loc, "data", axis=1, tiled=True)
        wo_f = lax.all_gather(wo_loc, "data", axis=2, tiled=True)
        e = router.shape[-1]
        e_loc = wi_f.shape[0]
        e0 = (lax.axis_index("model") * e_loc) if ep else 0

        t_loc = b_loc * s
        xf = x_loc.reshape(t_loc, d)
        gates = jax.nn.softmax(
            xf.astype(jnp.float32) @ router.astype(jnp.float32), axis=-1)
        weights, expert_idx = lax.top_k(gates, top_k)
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

        frac_tokens = jnp.zeros((e,), jnp.float32).at[
            expert_idx.reshape(-1)].add(1.0 / (t_loc * top_k))
        aux = e * jnp.sum(frac_tokens * gates.mean(0))
        for ax in dp:
            aux = lax.pmean(aux, ax)

        capacity = int(max(top_k, capacity_factor * t_loc * top_k / e))
        xe, slot, keep, token_sorted, w_sorted = _dispatch_local(
            xf, expert_idx, weights, e0, e_loc, capacity)

        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg_f))
             * jnp.einsum("ecd,edf->ecf", xe, wi_f))
        ye = jnp.einsum("ecf,efd->ecd", h, wo_f)
        yf = ye.reshape(e_loc * capacity, d)
        y_pairs = jnp.where(keep[:, None],
                            yf[jnp.minimum(slot, e_loc * capacity - 1)], 0.0)
        contrib = y_pairs * w_sorted[:, None].astype(y_pairs.dtype)
        y = jnp.zeros((t_loc, d), x_loc.dtype).at[token_sorted].add(contrib)
        # EP: each model shard produced its experts' share; TP-F: each
        # shard produced a partial over F.  Both finish with one psum.
        y = lax.psum(y, "model")
        return y.reshape(b_loc, s, d), aux

    if ep:
        wi_spec = P("model", "data", None)
        wo_spec = P("model", None, "data")
    else:
        wi_spec = P(None, "data", "model")
        wo_spec = P(None, "model", "data")

    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None), wi_spec, wi_spec,
                  wo_spec),
        out_specs=(P(dp, None, None), P()), check_vma=False,
    )

    def moe(x, router, wi, wg, wo):
        return smapped(x, router, wi, wg, wo)

    return moe
