"""Expert-parallel MoE dispatch via explicit all-to-all (shard_map).

§Perf iteration 8 found GSPMD lowers the constraint-hinted dispatch as
"all-gather every token to every expert group" — tokens x d x data_axis
bytes per MoE layer.  This module routes each token ONCE: tokens are binned
by destination expert shard on their home device, exchanged with a single
`all_to_all` over the ``model`` axis, computed against the LOCAL expert
slice, and returned by the mirror all_to_all; gate weighting and the
combine happen back on the token's home device.

Per-layer collective volume drops from O(T·d·n_model) to O(T·d·k·slack)
(~20x at solar's shapes — napkin math in EXPERIMENTS.md §Perf iter 8).

Caveats (by design, documented):
* fixed per-(src,dst) capacity: C_send = ceil(k·T_local/n_model · slack);
  overflow tokens are dropped exactly like capacity drops in the dense
  dispatch (load-balance loss keeps this rare);
* requires n_experts % model_axis == 0 and tokens % data_size == 0 —
  callers fall back to the constraint-hinted path otherwise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoESpec
from repro.models.layers import swiglu

def _local_expert_compute(xe, expert_ids, p, n_local, capacity):
    """Compute the local expert slice over received tokens.

    xe: (R, d) received tokens; expert_ids: (R,) LOCAL expert index (or -1
    for padding).  Gathers per-expert top-capacity rows, einsums, scatters
    back.  Returns (R, d).
    """
    r, d = xe.shape
    # one-hot priority: valid rows first
    prio = jnp.where(expert_ids[None, :] == jnp.arange(n_local)[:, None],
                     1.0, 0.0)                            # (E_l, R)
    cap = min(capacity, r)
    w, idx = jax.lax.top_k(prio, cap)                     # (E_l, cap)
    valid = w > 0.5
    rows = jnp.take(xe, idx.reshape(-1), axis=0).reshape(n_local, cap, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", rows, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", rows, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    ye = ye * valid[..., None].astype(ye.dtype)
    out = jnp.zeros((r, d), ye.dtype).at[idx.reshape(-1)].add(
        ye.reshape(-1, d), mode="drop")
    return out


def moe_ffn_a2a(x, p, spec: MoESpec, mesh, *, batch_axes=("data",),
                model_axis: str = "model", slack: float = 2.0):
    """Drop-in MoE FFN with explicit a2a dispatch.  x: (B, S, d).

    Must be traced under ``mesh``; x is assumed batch-sharded over
    ``batch_axes`` and replicated over ``model_axis``.
    """
    b, s, d = x.shape
    t = b * s
    e, k = spec.n_experts, spec.top_k
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = sizes[model_axis]
    n_data = 1
    for a in batch_axes:
        n_data *= sizes.get(a, 1)
    assert e % n_model == 0 and t % (n_data * n_model) == 0
    e_local = e // n_model
    # tokens are sharded over BOTH axes inside the shard_map (each device
    # owns t/(data*model) tokens and routes only those)
    t_local = t // (n_data * n_model)
    c_send = max(int(-(-k * t_local // n_model) * slack), 4)

    xf = x.reshape(t, d)
    # router (tiny): plain GSPMD
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    tok_axes = tuple(batch_axes) + (model_axis,)

    def body(xf_l, gi_l, gv_l, wg, wu, wd):
        # xf_l: (t_local, d); gi_l/gv_l: (t_local, k); w*: (e_local, ...)
        tl = xf_l.shape[0]
        flat_expert = gi_l.reshape(-1)                    # (tl*k,)
        flat_tok = jnp.repeat(jnp.arange(tl), k)
        flat_w = gv_l.reshape(-1)
        dst = flat_expert // e_local                      # (tl*k,)
        # per destination shard: pick up to c_send assignments
        prio = jnp.where(dst[None, :] == jnp.arange(n_model)[:, None],
                         flat_w[None, :] + 1e-6, 0.0)     # (n_model, tl*k)
        sel_w, sel = jax.lax.top_k(prio, min(c_send, tl * k))
        valid = sel_w > 0.0                               # (n_model, c_send)
        tok_rows = jnp.take(flat_tok, sel.reshape(-1)).reshape(n_model, -1)
        exp_ids = jnp.take(flat_expert, sel.reshape(-1)).reshape(n_model, -1)
        send = jnp.take(xf_l, tok_rows.reshape(-1), axis=0) \
            .reshape(n_model, -1, d)                      # (n_model, C, d)
        exp_local = jnp.where(valid, exp_ids % e_local, -1)

        # exchange tokens + local-expert ids across the model axis
        recv = jax.lax.all_to_all(send, model_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        recv_eid = jax.lax.all_to_all(exp_local, model_axis, split_axis=0,
                                      concat_axis=0, tiled=False)
        rr = recv.reshape(-1, d)
        # capacity = all received rows: no second-stage drops (R ~ k*tl*slack)
        ye = _local_expert_compute(
            rr, recv_eid.reshape(-1),
            {"w_gate": wg, "w_up": wu, "w_down": wd},
            e_local, capacity=rr.shape[0])
        ye = ye.reshape(n_model, -1, d)

        # mirror exchange back to the token home shards
        back = jax.lax.all_to_all(ye, model_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # weighted combine at home
        contrib = back * (sel_w * valid).reshape(n_model, -1, 1) \
            .astype(back.dtype)
        out = jnp.zeros((tl, d), back.dtype).at[tok_rows.reshape(-1)].add(
            contrib.reshape(-1, d), mode="drop")
        return out        # home tokens are disjoint across devices

    shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(tok_axes, None), P(tok_axes, None), P(tok_axes, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=P(tok_axes, None),
        check_vma=False)
    out = shard(xf, gate_idx, gate_vals.astype(xf.dtype),
                p["w_gate"], p["w_up"], p["w_down"])

    if spec.n_shared:
        out = out + swiglu(xf, **p["shared"])

    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_idx, e, dtype=jnp.float32).sum(1), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    lb_loss = e * jnp.sum(frac_tokens * frac_probs) / k
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return out.reshape(b, s, d).astype(x.dtype), \
        {"lb_loss": lb_loss, "z_loss": z_loss}
