"""Mixture-of-Experts layer with sort-based token dispatch.

Design notes (scaling to kimi-k2: 384 experts, top-8, 1T params):

  * Dispatch is GATHER-based, not one-hot-einsum based.  The GShard
    dispatch einsum materialises a (tokens x experts x capacity) one-hot
    and costs tokens*E*C*d "fake" FLOPs; at 384 experts that is both the
    memory and the compute roofline killer.  Instead we compute each
    token's slot with an argsort + rank (pure integer ops), scatter
    tokens into the (E, C, d) buffer, run the batched expert FFN, and
    gather/segment-sum back.  HLO FLOPs then count only the real expert
    matmuls, keeping MODEL_FLOPS / HLO_FLOPs honest.
  * Capacity-and-drop (cf * T * top_k / E slots per expert) bounds all
    shapes statically for jit; dropped tokens fall back to the residual
    stream (standard Switch behaviour).
  * Expert weights carry a leading E axis; the launcher shards it over
    the 'model' mesh axis (expert parallelism) and the optimizer state
    over 'data' (ZeRO-1), see repro/parallel/sharding.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import MoEConfig


def init_moe_params(key, d_model: int, moe: MoEConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 5)
    e, h = moe.n_experts, moe.d_expert
    si, so = d_model ** -0.5, h ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d_model, e), jnp.float32) * si,
        "w_gate": jax.random.normal(ks[1], (e, d_model, h), dtype) * si,
        "w_up": jax.random.normal(ks[2], (e, d_model, h), dtype) * si,
        "w_down": jax.random.normal(ks[3], (e, h, d_model), dtype) * so,
    }
    if moe.n_shared_experts:
        hs = moe.n_shared_experts * h
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": jax.random.normal(k1, (d_model, hs), dtype) * si,
            "w_up": jax.random.normal(k2, (d_model, hs), dtype) * si,
            "w_down": jax.random.normal(k3, (hs, d_model), dtype) * so,
        }
    return p


def _capacity(tokens: int, moe: MoEConfig) -> int:
    c = int(tokens * moe.top_k * moe.capacity_factor / moe.n_experts) + 1
    return max(4, -(-c // 4) * 4)    # round up to a multiple of 4


def _route_tokens(router: jnp.ndarray, tokens: jnp.ndarray, moe: MoEConfig,
                  cap: int):
    """Top-k routing + sort-based slot assignment (integer only).

    Shared by the dense (``moe_block``) and coded (``CodedMoE``) expert
    paths so the dispatch semantics cannot diverge.  Returns
    ``(aux, fp, tok_id, keep, dest)``: the Switch load-balancing aux
    loss, flattened combine weights, token ids, capacity-keep mask and
    slot destinations (OOB -> dropped).
    """
    t = tokens.shape[0]
    e, k = moe.n_experts, moe.top_k
    logits = jnp.einsum("td,de->te", tokens.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                  # (t, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch): top-1 share x mean prob
    frac_tokens = jnp.mean(jax.nn.one_hot(top_e[:, 0], e), axis=0)
    aux = e * jnp.sum(frac_tokens * probs.mean(axis=0))

    fe = top_e.reshape(-1)                                   # (t*k,)
    fp = top_p.reshape(-1)
    tok_id = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(fe, stable=True)
    counts = jnp.bincount(fe, length=e)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    ranks = jnp.arange(t * k) - starts[fe[order]]
    pos = jnp.zeros(t * k, jnp.int32).at[order].set(ranks.astype(jnp.int32))
    keep = pos < cap
    dest = jnp.where(keep, fe * cap + pos, e * cap)          # OOB -> dropped
    return aux, fp, tok_id, keep, dest


def _combine_slots(ye: jnp.ndarray, fp, tok_id, keep, dest, t: int, dtype
                   ) -> jnp.ndarray:
    """Expert outputs (E, C, d) -> per-token combine (t, d)."""
    n_slots = ye.shape[0] * ye.shape[1]
    y_flat = ye.reshape(n_slots, -1)
    y_slot = jnp.where(keep[:, None],
                       y_flat[jnp.minimum(dest, n_slots - 1)], 0.0)
    return jax.ops.segment_sum(y_slot * fp[:, None].astype(dtype),
                               tok_id, num_segments=t)


def _shared_expert(sp: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    gs = jnp.einsum("td,dh->th", tokens, sp["w_gate"])
    us = jnp.einsum("td,dh->th", tokens, sp["w_up"])
    return jnp.einsum("th,hd->td", jax.nn.silu(gs) * us, sp["w_down"])


def moe_block(p: dict, x: jnp.ndarray, moe: MoEConfig
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    b, s, d = x.shape
    t = b * s
    e = moe.n_experts
    cap = _capacity(t, moe)
    tokens = x.reshape(t, d)

    aux, fp, tok_id, keep, dest = _route_tokens(p["router"], tokens, moe, cap)

    # --- dispatch -> expert FFN -> combine ----------------------------------
    from ..parallel.ctx import shard  # noqa: PLC0415

    buf = jnp.zeros((e * cap, d), x.dtype).at[dest].set(
        tokens[tok_id], mode="drop")
    xe = shard("moe_xe", buf.reshape(e, cap, d))
    # FSDP cut point: regather the expert weights over the 'data' axis
    # once per layer instead of letting GSPMD contract over the sharded
    # d_model dim (which all-reduces giant (E,C,h) partials -- SPerf).
    w_gate = shard("moe_w", p["w_gate"])
    w_up = shard("moe_w", p["w_up"])
    w_down = shard("moe_w", p["w_down"])
    g = jnp.einsum("ecd,edh->ech", xe, w_gate)
    u = jnp.einsum("ecd,edh->ech", xe, w_up)
    ye = jnp.einsum("ech,ehd->ecd", jax.nn.silu(g) * u, w_down)
    out = _combine_slots(ye, fp, tok_id, keep, dest, t, x.dtype)

    if moe.n_shared_experts:
        out = out + _shared_expert(p["shared"], tokens)

    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path
# ---------------------------------------------------------------------------


def moe_block_ep(p: dict, x: jnp.ndarray, moe: MoEConfig, mesh,
                 dp_axes: tuple[str, ...], model_axis: str
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """shard_map MoE: the scalable EP execution (see EXPERIMENTS.md SPerf).

    Per (data x model) device:
      * route ALL local tokens (router compute duplicated across the
        model axis -- negligible);
      * build the dispatch buffer ONLY for this model-shard's
        E/model_parallelism experts -- pure local integer ops, no
        collectives (vs (T, d)-scale all-reduces when GSPMD partitions
        the global scatter);
      * all-gather this shard's expert weights over 'data' (FSDP
        regather, once per layer);
      * FFN + local combine, then ONE psum over 'model' sums expert
        contributions into the (T_local, d) output.

    Capacity is enforced per data shard (GShard "local groups"
    semantics).
    """
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415

    b, s, d = x.shape
    e, k = moe.n_experts, moe.top_k
    n_model = mesh.shape[model_axis]
    if e % n_model:
        return moe_block(p, x, moe)   # EP needs E % model == 0
    e_local = e // n_model

    def inner(router, w_gate, w_up, w_down, xx):
        bl, sl, _ = xx.shape
        t = bl * sl
        cap = _capacity(t, moe)
        toks = xx.reshape(t, d)
        # weights arrive as (E_local, d_local, h): regather over data
        w_g = jax.lax.all_gather(w_gate, dp_axes[-1], axis=1, tiled=True)
        w_u = jax.lax.all_gather(w_up, dp_axes[-1], axis=1, tiled=True)
        w_d = jax.lax.all_gather(w_down, dp_axes[-1], axis=2, tiled=True)

        logits = jnp.einsum("td,de->te", toks.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        frac = jnp.mean(jax.nn.one_hot(top_e[:, 0], e), axis=0)
        aux = e * jnp.sum(frac * probs.mean(axis=0))
        aux = jax.lax.pmean(aux, dp_axes)
        aux = jax.lax.pmean(aux, model_axis)

        fe = top_e.reshape(-1)
        fp = top_p.reshape(-1).astype(xx.dtype)
        tok_id = jnp.repeat(jnp.arange(t), k)
        order = jnp.argsort(fe, stable=True)
        counts = jnp.bincount(fe, length=e)
        starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        ranks = jnp.arange(t * k) - starts[fe[order]]
        pos = jnp.zeros(t * k, jnp.int32).at[order].set(
            ranks.astype(jnp.int32))
        # keep only this shard's experts
        e0 = jax.lax.axis_index(model_axis) * e_local
        mine = (fe >= e0) & (fe < e0 + e_local) & (pos < cap)
        dest = jnp.where(mine, (fe - e0) * cap + pos, e_local * cap)

        buf = jnp.zeros((e_local * cap, d), xx.dtype).at[dest].set(
            toks[tok_id], mode="drop")
        xe = buf.reshape(e_local, cap, d)
        g = jnp.einsum("ecd,edh->ech", xe, w_g)
        u = jnp.einsum("ecd,edh->ech", xe, w_u)
        ye = jnp.einsum("ech,ehd->ecd", jax.nn.silu(g) * u, w_d)
        y_flat = ye.reshape(e_local * cap, d)
        y_slot = jnp.where(mine[:, None],
                           y_flat[jnp.minimum(dest, e_local * cap - 1)], 0.0)
        out = jax.ops.segment_sum(y_slot * fp[:, None], tok_id,
                                  num_segments=t)
        out = jax.lax.psum(out.astype(jnp.float32), model_axis)
        return out.astype(xx.dtype).reshape(bl, sl, d), aux

    from ..parallel.ctx import auto_mesh  # noqa: PLC0415

    fn = jax.shard_map(
        inner, mesh=auto_mesh(mesh),
        in_specs=(P(None, None), P(model_axis, dp_axes[-1], None),
                  P(model_axis, dp_axes[-1], None),
                  P(model_axis, None, dp_axes[-1]),
                  P(dp_axes, None, None)),
        out_specs=(P(dp_axes, None, None), P()),
        check_vma=False,
    )
    out, aux = fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)

    if moe.n_shared_experts:
        sp = p["shared"]
        toks = x.reshape(-1, d)
        gs = jnp.einsum("td,dh->th", toks, sp["w_gate"])
        us = jnp.einsum("td,dh->th", toks, sp["w_up"])
        out = out + jnp.einsum("th,hd->td", jax.nn.silu(gs) * us,
                               sp["w_down"]).reshape(b, s, d)
    return out, aux


def moe_apply(p: dict, x: jnp.ndarray, moe: MoEConfig):
    """Dispatch to the EP path when an expert-parallel context is set."""
    from ..parallel.ctx import ep_context  # noqa: PLC0415

    ep = ep_context()
    if ep is not None:
        mesh, dp, model_axis = ep
        return moe_block_ep(p, x, moe, mesh, dp, model_axis)
    return moe_block(p, x, moe)


# ---------------------------------------------------------------------------
# Straggler-resilient expert FFN (coded plan path)
# ---------------------------------------------------------------------------


class CodedMoE:
    """Expert FFN with straggler resilience: every expert weight matmul
    runs through a precompiled ``repro.api.CodedPlan``.

    The edge scenario: each expert's three (d x h / h x d) matrices are
    plan-compiled once (scheme + encoding + packed shards + backend) for
    ``n_workers`` virtual workers tolerating ``stragglers`` losses per
    matmul -- the MoE analogue of the coded LM head.  ``backend="auto"``
    measures each weight's block density, so dense experts run the
    reference einsum while pruned/sparse experts get the packed
    block-sparse path for free (the ROADMAP density crossover, per
    operator).

    Routing (top-k, sort-based slotting, capacity drop) is identical to
    ``moe_block`` -- integer work that is not worth coding.  Per step a
    single ``done`` mask applies to all expert matmuls (the workers are
    the same physical devices); outputs match ``moe_block`` to fp32
    tolerance under any <= s straggler pattern.

    Pass ``fleet=`` (a ``repro.api.fleet.CodedFleet``) to *dispatch*
    the expert matmuls instead of computing them in-process: every
    expert plan attaches to the shared session (the same workers that
    serve the coded LM head), and the forward pipelines rounds through
    async futures -- all experts' gate+up products go in flight
    together, each expert's down product is submitted the moment its
    activation is ready.  The fleet's owner closes it; ``detach()``
    withdraws this layer's plans early.
    """

    def __init__(self, p: dict, moe: MoEConfig, n_workers: int = 6,
                 stragglers: int = 2, seed: int = 0,
                 scheme: str = "proposed", backend: str | None = "auto",
                 fleet=None):
        from ..api.plan import compile_plan  # noqa: PLC0415 - layering
        from ..api.schemes import make_scheme  # noqa: PLC0415

        self.p = p
        self.moe = moe
        self.n = n_workers
        self.s = stragglers
        self.fleet = fleet
        sch = make_scheme(scheme, n=n_workers, k_A=n_workers - stragglers)
        e = moe.n_experts

        def plans(w):          # w: (E, din, dout) stacked expert weights
            built = [compile_plan(w[i], scheme=sch, seed=seed + i,
                                  backend=backend) for i in range(e)]
            if fleet is None:
                return built
            return [fleet.attach(pl) for pl in built]

        self.gate = plans(p["w_gate"])
        self.up = plans(p["w_up"])
        self.down = plans(p["w_down"])

    def backends(self) -> list[str]:
        """Resolved backend per expert-gate plan (density may differ)."""
        return [pl.plan.backend if self.fleet is not None else pl.backend
                for pl in self.gate]

    def detach(self) -> None:
        """Withdraw this layer's plans from the shared fleet (no-op for
        the in-process path)."""
        if self.fleet is None:
            return
        for handle in self.gate + self.up + self.down:
            handle.detach()

    def __call__(self, x: jnp.ndarray, done: jnp.ndarray | None = None
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """x (B, S, d) -> (out, aux); ``done`` masks the coded workers."""
        p, moe = self.p, self.moe
        b, s, d = x.shape
        t = b * s
        e = moe.n_experts
        cap = _capacity(t, moe)
        tokens = x.reshape(t, d)

        aux, fp, tok_id, keep, dest = _route_tokens(
            p["router"], tokens, moe, cap)
        buf = jnp.zeros((e * cap, d), x.dtype).at[dest].set(
            tokens[tok_id], mode="drop")
        xe = buf.reshape(e, cap, d)

        if self.fleet is not None:
            outs = self._dispatch_experts(xe, done)
        else:
            # --- coded expert FFN: three plan.matvec calls per expert --
            outs = []
            for i in range(e):
                g = self.gate[i].matvec(xe[i], done)      # (cap, h)
                u = self.up[i].matvec(xe[i], done)
                y = self.down[i].matvec(
                    (jax.nn.silu(g) * u).astype(xe.dtype), done)
                outs.append(y)
        ye = jnp.stack(outs).astype(x.dtype)              # (e, cap, d)
        out = _combine_slots(ye, fp, tok_id, keep, dest, t, x.dtype)

        if moe.n_shared_experts:
            out = out + _shared_expert(p["shared"], tokens)
        return out.reshape(b, s, d), aux

    def _dispatch_experts(self, xe: jnp.ndarray, done) -> list:
        """Fleet path: pipeline every expert's FFN through futures.

        All gate+up rounds go in flight at once; each down round is
        submitted as soon as its expert's activation is available, so
        expert i+1's gate product overlaps expert i's down product on
        the shared workers.
        """
        e = xe.shape[0]
        gate_f = [self.gate[i].submit_matvec(xe[i], done) for i in range(e)]
        up_f = [self.up[i].submit_matvec(xe[i], done) for i in range(e)]
        down_f = []
        for i in range(e):
            h = (jax.nn.silu(gate_f[i].result())
                 * up_f[i].result()).astype(xe.dtype)
            down_f.append(self.down[i].submit_matvec(h, done))
        return [f.result() for f in down_f]
