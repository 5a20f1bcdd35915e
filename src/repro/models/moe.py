"""Mixture-of-Experts FFN: top-k router + sort-based dropless-ish dispatch.

Design (DESIGN.md §7): the GShard one-hot dispatch tensor [N, E, C] is
infeasible at the assigned shapes, so dispatch is a *sort*:

    1. router: logits [N, E] → top-k (expert, weight) records (N·k records)
    2. sort records by expert id; rank-in-segment gives per-expert slots
    3. scatter tokens into capacity buckets  x_e [E, C, d]
    4. two batched einsums with the expert weights (E is the EP axis —
       sharded over "model"; GSPMD turns scatter/gather across the token
       and expert shardings into the dispatch collectives)
    5. scatter-add weighted outputs back to token order.

Tokens beyond an expert's capacity C = ceil(k·N·cf/E) are dropped (standard
capacity-factor semantics; counted in aux stats). Router runs in f32; an
auxiliary load-balancing loss (Switch-style) is returned for training.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.models.common import Px, dense_init
from repro.utils import boundaries_from_keys, rank_in_segment


def init_moe(key, cfg, dtype=jnp.bfloat16, ep: int = 16):
    d, f = cfg.d_model, cfg.d_ff
    e = cfg.moe.experts_padded(ep)
    ks = jax.random.split(key, 4)
    return {
        "router": Px(dense_init(ks[0], (d, e), 0, jnp.float32), ("embed", "experts")),
        "wi": Px(dense_init(ks[1], (e, d, f), 1, dtype), ("experts", "embed", "ff")),
        "wg": Px(dense_init(ks[2], (e, d, f), 1, dtype), ("experts", "embed", "ff")),
        "wo": Px(dense_init(ks[3], (e, f, d), 1, dtype), ("experts", "ff", "embed")),
    }


def _router_probs(router_w, xt, e_real: int):
    """Masked router softmax in f32 (padding experts get -inf logits)."""
    e_pad = router_w.shape[-1]
    logits = xt.astype(jnp.float32) @ router_w
    if e_pad > e_real:
        logits = jnp.where(jnp.arange(e_pad)[None, :] >= e_real, -1e30, logits)
    return jax.nn.softmax(logits, axis=-1)


def _load_balance_aux(probs, e_real: int):
    """Switch-style load-balance loss from the (masked) router probs."""
    e_pad = probs.shape[-1]
    frac_tokens = jnp.mean(
        jax.nn.one_hot(jnp.argmax(probs, axis=-1), e_pad, dtype=jnp.float32),
        axis=0,
    )
    frac_probs = jnp.mean(probs, axis=0)
    return e_real * jnp.sum(frac_tokens * frac_probs)


def apply_moe(p, x, cfg, rules=None, capacity_factor: float | None = None):
    """Dispatch selector: GSPMD baseline vs explicit-a2a EP (§Perf iter. 1).

    The a2a path requires token shards that *vary* along the EP axis
    (seq divisible by the "model" axis) — single-token decode keeps the
    GSPMD path, where the dispatch buffers are small anyway."""
    impl = cfg.moe.impl if cfg.moe is not None else "gspmd"
    if (impl == "a2a" and rules is not None and rules.mesh is not None
            and "model" in rules.mesh.axis_names
            and x.shape[1] % rules.mesh.shape["model"] == 0):
        return apply_moe_a2a(p, x, cfg, rules, capacity_factor)
    return apply_moe_gspmd(p, x, cfg, rules, capacity_factor)


def apply_moe_gspmd(p, x, cfg, rules=None, capacity_factor: float | None = None):
    """x: [B, S, d] → ([B, S, d], aux dict)."""
    b, s, d = x.shape
    n = b * s
    e_real = cfg.moe.num_experts
    e_pad = p["router"].shape[-1]
    k = cfg.moe.top_k
    cf = capacity_factor or cfg.moe.capacity_factor
    if s == 1:
        # single-token decode: dropless (buffers are tiny; capacity drops
        # would make decode diverge from the training forward)
        cap = n * k
    else:
        cap = max(int(k * n * cf / e_real), 1)

    xt = x.reshape(n, d)
    probs = _router_probs(p["router"], xt, e_real)
    top_w, top_e = jax.lax.top_k(probs, k)  # [N, k]
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch -------------------------------------------
    # permutation computed on integer keys only (argsort is gradient-free);
    # values are then *gathered*, keeping the combine path differentiable.
    rec_e = top_e.reshape(-1).astype(jnp.int32)  # [N·k]
    rec_t = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    rec_w = top_w.reshape(-1).astype(jnp.float32)
    perm = jnp.argsort(rec_e * (n + 1) + rec_t)  # stable (expert, token) order
    e_s = rec_e[perm]
    t_s = rec_t[perm]
    w_s = rec_w[perm]
    slot = rank_in_segment(boundaries_from_keys(e_s))
    ok = slot < cap
    flat = jnp.where(ok, e_s * cap + slot, e_pad * cap)  # OOB → dropped
    x_e = jnp.zeros((e_pad * cap + 1, d), x.dtype)
    x_e = x_e.at[flat].set(xt[t_s], mode="drop")[:-1].reshape(e_pad, cap, d)
    if rules is not None:
        x_e = rules.constrain(x_e, "experts", None, None)

    # ---- expert computation (E = EP axis) ------------------------------
    h = jnp.einsum("ecd,edf->ecf", x_e, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", x_e, p["wg"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
    y_e = jnp.einsum("ecf,efd->ecd", h, p["wo"])
    if rules is not None:
        y_e = rules.constrain(y_e, "experts", None, None)

    # ---- combine back to token order ------------------------------------
    # clamped gather, no sentinel row: dropped records read a live row and
    # are masked to an exact 0 (select, not multiply — robust to inf/nan
    # in expert outputs). A concat-then-gather sentinel here is miscompiled
    # by the XLA SPMD partitioner on meshes with a data axis — every output
    # gets multiplied by the data-axis size.
    y_flat = y_e.reshape(e_pad * cap, d)
    src = jnp.where(ok, flat, 0)
    gathered = jnp.where(ok[:, None], y_flat[src].astype(jnp.float32), 0.0)
    contrib = gathered * jnp.where(ok, w_s, 0.0)[:, None]
    y = jnp.zeros((n, d), jnp.float32).at[t_s].add(contrib)

    aux_loss = _load_balance_aux(probs, e_real)
    dropped = jnp.sum(~ok) / jnp.maximum(n * k, 1)
    return y.reshape(b, s, d).astype(x.dtype), {
        "moe_aux": aux_loss,
        "moe_drop_frac": dropped,
    }


# ---------------------------------------------------------------------------
# §Perf iteration 1: explicit expert-parallel dispatch under shard_map
# ---------------------------------------------------------------------------
#
# Hypothesis (EXPERIMENTS.md §Perf): under pure GSPMD the sort-based
# scatter/gather between token-sharded activations and expert-sharded
# buffers has data-dependent indices, so the partitioner falls back to
# all-gather/all-reduce of the *full* dispatch buffers — ~10 TB/device of
# collective traffic per moonshot prefill step. The classic fix is the
# MoE all-to-all: route each token shard directly to the EP rank that owns
# its expert. Payload per device per layer becomes k·n_local·cf·d bf16
# each way (~126 MB for moonshot prefill) — a ~3 orders-of-magnitude cut.
#
# Layout: tokens enter sharded [B/dp, S/tp, d]; experts are sharded over
# "model" (e_local = E/tp per rank). Each rank:
#   1. routes its n_local tokens (router weights are replicated),
#   2. packs per-EP-group buckets [tp, cap_r, d] (capacity-dropped, counted),
#   3. all_to_all over "model" → receives the tokens destined to its experts,
#   4. local sort-based dispatch over e_local experts (second capacity),
#   5. all_to_all back and weighted scatter-add into token order.
# Every step is differentiable (argsort keys are gradient-free; data moves
# via gather/scatter-add and a2a, both with well-defined transposes).


def _dispatch_to_buckets(vals, keys, n_buckets: int, cap: int, fill=0.0):
    """Scatter ``vals`` rows into [n_buckets, cap, ...] by ``keys`` (sorted
    stable order); returns (buckets, sort_order, flat_slot_per_row, ok_mask)."""
    order = jnp.argsort(keys, stable=True)
    k_s = keys[order]
    slot = rank_in_segment(boundaries_from_keys(k_s))
    ok = (slot < cap) & (k_s < n_buckets)
    flat = jnp.where(ok, k_s * cap + slot, n_buckets * cap)
    out_shape = (n_buckets * cap + 1,) + vals.shape[1:]
    buckets = jnp.full(out_shape, fill, vals.dtype)
    buckets = buckets.at[flat].set(vals[order], mode="drop")[:-1]
    return buckets.reshape((n_buckets, cap) + vals.shape[1:]), order, flat, ok


def apply_moe_a2a(p, x, cfg, rules, capacity_factor: float | None = None):
    """Explicit-collective EP MoE (see header). Same numerics contract as
    the GSPMD path (capacity drops differ only in which tokens overflow)."""
    mesh = rules.mesh
    names = mesh.axis_names
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    assert x.shape[1] % mesh.shape["model"] == 0, "a2a needs seq % EP == 0"
    seq_ax = "model"
    ep = mesh.shape["model"]
    e_pad = p["router"].shape[-1]
    e_real = cfg.moe.num_experts
    k = cfg.moe.top_k
    cf = capacity_factor or cfg.moe.capacity_factor
    assert e_pad % ep == 0, (e_pad, ep)
    e_local = e_pad // ep

    from jax.sharding import PartitionSpec as P

    x_spec = P(dp_axes if dp_axes else None, seq_ax, None)
    p_specs = {
        "router": P(None, None),
        "wi": P("model", None, None),
        "wg": P("model", None, None),
        "wo": P("model", None, None),
    }

    def body(params, xl):
        b_l, s_l, d = xl.shape
        n_l = b_l * s_l
        cap_r = max(int(k * n_l * cf / ep), 1)       # per-destination-rank
        cap_e = max(int(2 * ep * cap_r / e_local), 1)  # local per-expert

        xt = xl.reshape(n_l, d)
        probs = _router_probs(params["router"], xt, e_real)
        top_w, top_e = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)

        rec_e = top_e.reshape(-1).astype(jnp.int32)           # [N·k]
        rec_t = jnp.repeat(jnp.arange(n_l, dtype=jnp.int32), k)
        rec_w = top_w.reshape(-1).astype(jnp.float32)
        grp = rec_e // e_local                                 # EP rank

        # ---- pack per-rank buckets and route -------------------------------
        payload = xt[rec_t]                                    # [N·k, d]
        buckets, order, flat, ok = _dispatch_to_buckets(payload, grp, ep, cap_r)
        eid_rows = jnp.where(ok, (rec_e % e_local)[order], -1).astype(jnp.int32)
        eid_buckets = jnp.full((ep * cap_r + 1,), -1, jnp.int32)
        eid_buckets = eid_buckets.at[flat].set(eid_rows, mode="drop")[:-1]
        recv = jax.lax.all_to_all(buckets, "model", 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(
            eid_buckets.reshape(ep, cap_r), "model", 0, 0, tiled=False)
        recv = recv.reshape(ep * cap_r, d)
        recv_eid = recv_eid.reshape(ep * cap_r)

        # ---- local expert compute (second, local dispatch) ------------------
        key2 = jnp.where(recv_eid >= 0, recv_eid, e_local)
        x_e, order2, flat2, ok2 = _dispatch_to_buckets(recv, key2, e_local,
                                                       cap_e)
        wi, wg, wo = params["wi"], params["wg"], params["wo"]
        h = jnp.einsum("ecd,edf->ecf", x_e, wi)
        g = jnp.einsum("ecd,edf->ecf", x_e, wg)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x_e.dtype) * h
        y_e = jnp.einsum("ecf,efd->ecd", h, wo).reshape(e_local * cap_e, d)

        # undo local dispatch: back to received-slot order
        y_pad = jnp.concatenate([y_e, jnp.zeros((1, d), y_e.dtype)])
        y_recv = jnp.zeros((ep * cap_r, d), y_e.dtype)
        y_recv = y_recv.at[order2].set(
            y_pad[jnp.minimum(flat2, e_local * cap_e)]
        )

        # ---- route back and combine -----------------------------------------
        back = jax.lax.all_to_all(y_recv.reshape(ep, cap_r, d), "model", 0, 0,
                                  tiled=False).reshape(ep * cap_r, d)
        back_pad = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)])
        per_rec = back_pad[jnp.minimum(flat, ep * cap_r)]      # sorted order
        contrib = per_rec.astype(jnp.float32) * jnp.where(
            ok, rec_w[order], 0.0)[:, None]
        y = jnp.zeros((n_l, d), jnp.float32).at[rec_t[order]].add(contrib)

        # ---- drop accounting (metric only — no gradient) --------------------
        # token shards vary over dp axes AND the EP ("model") axis
        all_axes = dp_axes + ("model",)
        drop1 = jnp.sum(~ok) / jnp.maximum(n_l * k, 1)
        # ok2 is False for both overflowed AND padding slots — only count
        # slots that carried a real token (recv_eid ≥ 0)
        n_valid2 = jnp.sum(recv_eid >= 0)
        drop2 = (n_valid2 - jnp.sum(ok2)) / jnp.maximum(n_l * k, 1)
        dropped = jax.lax.stop_gradient(
            jax.lax.pmean(drop1 + drop2, all_axes))
        return y.reshape(b_l, s_l, d).astype(xl.dtype), dropped

    sharded = shard_map(
        body, mesh=mesh,
        in_specs=(p_specs, x_spec),
        out_specs=(x_spec, P()),
    )
    y, dropped = sharded(
        {k_: p[k_] for k_ in ("router", "wi", "wg", "wo")}, x
    )

    # Load-balance aux loss, recomputed outside the shard_map from the
    # (replicated) router: per-token quantities mean-reduce identically to
    # the per-shard pmean, the router matmul is cheap, and the shard_map
    # keeps y as its only differentiable output — this jax's shard_map
    # transpose cannot take symbolic-zero cotangents for extra outputs.
    xt = x.reshape(-1, x.shape[-1])
    aux = _load_balance_aux(_router_probs(p["router"], xt, e_real), e_real)
    return y, {"moe_aux": aux, "moe_drop_frac": dropped}
