"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

Reference position: Horovod ships the PRIMITIVE this is built on —
``hvd.alltoall`` for DLRM-style embedding exchange (SURVEY.md §2c
"expert/embedding parallel via alltoall", BASELINE config #5) — but no MoE
layer; this module is the beyond-parity model family that turns the
primitive into a working sparse layer, TPU-first:

- **Static shapes everywhere** (XLA requirement): Switch-Transformer-style
  capacity-factor routing — every expert processes exactly ``capacity``
  token slots per source rank; over-capacity tokens are dropped (their
  output is the residual identity), under-capacity slots are zero padding.
- **Dispatch/combine are einsums** against a one-hot dispatch mask (the
  standard TPU formulation — no gather/scatter, everything rides the MXU).
- **Expert parallelism**: experts are sharded over ``ep``; the dispatched
  [E, C, D] buffer is exchanged with ONE ``lax.all_to_all`` over ICI so
  each rank runs only its local experts on every rank's tokens, and a
  second all_to_all brings expert outputs home (exactly the exchange the
  reference's DLRM config does for embeddings).
- **Load-balancing auxiliary loss** (Shazeer/Switch): mean(gate fraction ·
  token fraction) · E, summed across ranks by the caller's loss psum.

Layout: tokens ``[S, D]`` per rank (callers flatten [B, T]); experts'
FFN params ``{"w1": [E, D, F], "w2": [E, F, D]}`` stacked on the expert
axis — shard over ``ep`` with ``param_specs``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P
from .. import trace
from ..compat import axis_size as compat_axis_size


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int = 64
    d_ff: int = 128
    n_experts: int = 8
    capacity_factor: float = 1.25
    ep_axis: Optional[str] = "ep"      # None = all experts local
    router_noise: float = 0.0          # jitter std during training
    # Routing family: "tokens" = token-choice (each token picks its
    # top-k experts; Switch/GShard) — "expert_choice" = each expert
    # picks its top-C tokens (Zhou et al. 2022): perfect static load
    # balance by construction (every expert exactly full, no aux loss
    # needed), tokens may be served by 0..E experts (0 ⇒ residual
    # identity, like a capacity drop).
    router_mode: str = "tokens"
    # Experts per token: 1 = Switch (raw top-1 gate), k>=2 = GShard-style
    # top-k with gates NORMALIZED over the selected experts.  Token-choice
    # only (expert_choice fixes fan-in via capacity instead).
    router_top_k: int = 1
    # ST-MoE router z-loss weight (mean logsumexp(logits)^2): keeps router
    # logits small/stable in bf16 training.  0 = off.  Applied by the
    # training paths (lm_loss here, llama.loss_fn) as an ABSOLUTE weight,
    # like aux_weight.
    router_z_weight: float = 0.0
    # SwiGLU experts (Mixtral / the dense llama MLP shape): each expert
    # gains an up-projection w3 and computes (silu(x·w1) ⊙ (x·w3))·w2
    # instead of silu(x·w1)·w2.
    gated: bool = False
    dtype: Any = jnp.float32

    def capacity(self, tokens_per_rank: int) -> int:
        """Per-(source-rank, expert) token slots: static by construction.
        Top-k routing makes k assignments per token, so the slot budget
        scales with k (GShard's capacity definition)."""
        return max(1, int(np.ceil(tokens_per_rank * self.router_top_k
                                  / self.n_experts
                                  * self.capacity_factor)))


def init_params(cfg: MoEConfig, key) -> Dict:
    kr, k1, k2, k3 = jax.random.split(key, 4)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    s1, s2 = 1.0 / np.sqrt(D), 1.0 / np.sqrt(F)
    p = {
        "router": (jax.random.normal(kr, (D, E), jnp.float32) * s1
                   ).astype(cfg.dtype),
        "w1": (jax.random.normal(k1, (E, D, F), jnp.float32) * s1
               ).astype(cfg.dtype),
        "w2": (jax.random.normal(k2, (E, F, D), jnp.float32) * s2
               ).astype(cfg.dtype),
    }
    if cfg.gated:
        p["w3"] = (jax.random.normal(k3, (E, D, F), jnp.float32) * s1
                   ).astype(cfg.dtype)
    return p


def param_specs(cfg: MoEConfig) -> Dict:
    ep = cfg.ep_axis
    specs = {"router": P(), "w1": P(ep), "w2": P(ep)}
    if cfg.gated:
        specs["w3"] = P(ep)
    return specs


def _route(x, router_w, cfg: MoEConfig, rng: Optional[jax.Array]):
    """Top-k routing with static capacity (Switch for k=1, GShard for
    k>=2).

    Returns (dispatch [S, E, C] one-hot, combine [S, E, C] gate-weighted,
    aux_loss scalar, z_loss scalar).  Position of a token within its
    expert's capacity buffer comes from a cumsum over the expert's
    one-hot column, with later choices slotted AFTER all earlier
    choices' tokens (choice priority: a token's second expert never
    evicts another token's first) — deterministic, order-preserving,
    shape-static.

    Gates: k=1 uses the raw router probability (Switch); k>=2 normalizes
    the selected probabilities to sum to 1 (GShard) so the combined
    output is a convex mixture of the chosen experts.
    """
    S = x.shape[0]
    E = cfg.n_experts
    K = cfg.router_top_k
    if cfg.router_mode not in ("tokens", "expert_choice"):
        raise ValueError(f"router_mode must be 'tokens' or "
                         f"'expert_choice', got {cfg.router_mode!r}")
    if cfg.router_mode == "expert_choice" and K != 1:
        raise ValueError("expert_choice routing fixes per-expert fan-in "
                         "via capacity; router_top_k must stay 1")
    if not 1 <= K <= E:
        raise ValueError(f"router_top_k={K} must be in [1, {E}]")
    C = cfg.capacity(S)
    logits = (x.astype(jnp.float32)
              @ router_w.astype(jnp.float32))          # [S, E]
    if cfg.router_noise > 0.0:
        if rng is None:
            raise ValueError(
                "MoEConfig.router_noise > 0 requires threading rng= "
                "through moe_ffn / lm_loss / llama loss_fn")
        logits = logits + cfg.router_noise * jax.random.normal(
            rng, logits.shape, jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    # ST-MoE router z-loss: penalize large logits (logsumexp^2) — applied
    # by the caller with cfg.router_z_weight.
    z = jax.scipy.special.logsumexp(logits, axis=-1)   # [S]
    z_loss = jnp.mean(jnp.square(z))

    if cfg.router_mode == "expert_choice":
        if C > S:
            raise ValueError(f"expert_choice capacity {C} exceeds tokens "
                             f"{S}; lower capacity_factor")
        # Each expert takes its top-C tokens by router prob: [E, C]
        # scores + token ids.  top_k's gradient flows to the selected
        # probs through g; selection itself is non-differentiable, as in
        # every hard router.
        g, idx = lax.top_k(probs.T, C)                 # [E, C]
        dispatch = jax.nn.one_hot(idx, S,
                                  dtype=jnp.float32)   # [E, C, S]
        dispatch = dispatch.transpose(2, 0, 1)         # [S, E, C]
        combine = dispatch * g[None, :, :]
        # Perfectly balanced by construction: aux is identically its
        # floor (1.0-equivalent) — report 0 so aux_weight has no effect.
        return dispatch, combine, jnp.zeros((), jnp.float32), z_loss

    # Iterative argmax over the k choices; positions are cumulative
    # across choices via per-expert counts.
    masked = probs
    counts = jnp.zeros((E,), jnp.float32)
    disp_ks, gate_ks = [], []
    first_onehot = None
    for k in range(K):
        onehot = jax.nn.one_hot(jnp.argmax(masked, axis=-1), E,
                                dtype=jnp.float32)     # [S, E]
        if first_onehot is None:
            first_onehot = onehot
        gate_ks.append(jnp.sum(probs * onehot, axis=-1))   # raw prob [S]
        pos = ((jnp.cumsum(onehot, axis=0) + counts[None, :]) * onehot
               - 1.0)                                  # [S, E]
        pos_in_expert = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)
        keep = (pos_in_expert < C) & (pos_in_expert >= 0)
        pos_oh = jax.nn.one_hot(pos_in_expert, C, dtype=jnp.float32)
        disp_ks.append((onehot * keep[:, None])[:, :, None]
                       * pos_oh[:, None, :])           # [S, E, C]
        counts = counts + jnp.sum(onehot, axis=0)
        masked = masked * (1.0 - onehot)

    if K > 1:
        denom = sum(gate_ks) + 1e-9
        gate_ks = [g / denom for g in gate_ks]
    dispatch = sum(disp_ks)
    combine = sum(g[:, None, None] * d for g, d in zip(gate_ks, disp_ks))

    # Load-balance aux loss (Switch/GShard): fraction of tokens whose
    # FIRST choice is expert e vs fraction of router mass on e.
    token_frac = jnp.mean(first_onehot, axis=0)        # [E]
    prob_frac = jnp.mean(probs, axis=0)                # [E]
    aux = jnp.sum(token_frac * prob_frac) * E
    return dispatch, combine, aux, z_loss


def moe_ffn(x, params, cfg: MoEConfig,
            rng: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Apply the MoE FFN to per-rank tokens ``x [S, D]``.

    Inside shard_map with ``ep`` bound, ``params["w1"]/["w2"]`` are the
    LOCAL expert slab [E/ep, D, F] and the dispatch/return exchanges ride
    two ``lax.all_to_all``; without ``ep_axis`` every expert is local.
    Returns ``(y [S, D], aux_loss, z_loss)`` — dropped tokens yield zeros
    (callers add the residual).  ``rng`` is required iff
    ``cfg.router_noise > 0``.
    """
    S, D = x.shape
    E = cfg.n_experts
    C = cfg.capacity(S)
    dispatch, combine, aux, z_loss = _route(x, params["router"], cfg, rng)

    # [E, C, D] expert buffers (einsum dispatch — MXU, no scatter).
    buf = jnp.einsum("sec,sd->ecd", dispatch.astype(x.dtype), x)

    ep = compat_axis_size(cfg.ep_axis) if cfg.ep_axis else 1
    if ep > 1:
        if E % ep:
            raise ValueError(f"n_experts={E} must divide by ep={ep}")
        # Send each expert's buffer to its home rank; receive every rank's
        # buffers for OUR local experts, stacked along capacity:
        # [E, C, D] -> [E/ep, ep*C, D].
        buf = lax.all_to_all(buf, cfg.ep_axis, split_axis=0, concat_axis=1,
                             tiled=True)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w1"]))
    if cfg.gated:
        h = h * jnp.einsum("ecd,edf->ecf", buf, params["w3"])
    out = jnp.einsum("ecf,efd->ecd", h, params["w2"])

    if ep > 1:
        # Return trip: split the stacked capacity axis back per source
        # rank and send each chunk home -> [E, C, D] of OUR tokens'
        # outputs (chunk j went to rank j and comes back from rank j, so
        # expert-block order is preserved).
        out = lax.all_to_all(out, cfg.ep_axis, split_axis=1, concat_axis=0,
                             tiled=True)

    y = jnp.einsum("sec,ecd->sd", combine.astype(x.dtype), out)
    return y, aux.astype(jnp.float32), z_loss.astype(jnp.float32)


# ------------------------------------------------- dropless share-aware layer
@dataclasses.dataclass(frozen=True)
class DroplessMoEConfig:
    """A dropless top-k expert layer that is told which experts it holds,
    and what the model says of its experts.

    The router keeps the model's full width (``n_experts``) and its
    ``top_k``; this rank holds experts ``first_expert .. first_expert +
    experts_held`` and computes the part of the layer's result that they
    give for the tokens routed to them.  What the other experts would add
    is another rank's part: summed over all shares (what every rank
    computes alike — the shared expert, and with a latent its projections
    — counted once) the parts are the whole layer.  No exchange is made
    here — one share on one chip runs as it stands; the all-to-all that
    brings every rank's tokens to a share is ROADMAP queue 2 A's.  What a
    share costs follows the rows it holds, not the assignments made
    anywhere: :func:`dropless_moe_ffn` walks the sorted assignments in
    blocks sized from ``n_experts`` and ``experts_held`` and stops after
    the last held row.

    Fields of the model, not options of the system:

    ``scoring``      ``softmax``: probabilities over all experts, the
                     ``top_k`` largest renormalised to sum to 1.
                     ``sigmoid``: scores ``s = sigmoid(logits)``; the
                     ``top_k`` with the largest ``s + b`` are chosen (``b``
                     the selection bias ``router_bias``, a buffer no
                     gradient reaches) and weighed by their ``s`` over its
                     sum.  Either way the weights are then multiplied by
                     ``routed_scale``.
    ``expert_form``  ``swiglu``: three matrices, ``(silu(x W1) * x W3)
                     W2``.  ``relu2``: two, ``relu(x W1)^2 W2``.  The
                     shared expert has the same form.
    ``d_latent``     0: the routed experts act on ``d_model``.  Otherwise
                     they act in a latent of that width between ``w_down``
                     and ``w_up``, which every rank computes (``w_up`` is
                     linear: the shares' latent sums add).  The router and
                     the shared expert read ``d_model`` either way.
    ``shared_gate``  the shared expert's result times ``sigmoid(x w_g)``,
                     or as it is."""
    d_model: int = 64
    d_ff: int = 128                     # a routed expert's width
    n_experts: int = 8                  # the router's width
    top_k: int = 2
    first_expert: int = 0
    experts_held: Optional[int] = None  # None = all of them
    d_shared: int = 0                   # the shared expert's width, 0 = none
    dtype: Any = jnp.float32
    scoring: str = "softmax"
    routed_scale: float = 1.0
    expert_form: str = "swiglu"
    d_latent: int = 0
    shared_gate: bool = True

    @property
    def held(self) -> int:
        return self.n_experts if self.experts_held is None \
            else self.experts_held

    @property
    def d_expert_io(self) -> int:
        """The width a routed expert reads and writes."""
        return self.d_latent or self.d_model

    @property
    def gated(self) -> bool:
        return self.expert_form == "swiglu"

    def __post_init__(self):
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k={self.top_k} must be in "
                             f"[1, {self.n_experts}]")
        if not (0 <= self.first_expert
                and self.first_expert + self.held <= self.n_experts
                and self.held >= 1):
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.held}"
                f" are not among the {self.n_experts}")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring={self.scoring!r}: softmax or sigmoid")
        if self.expert_form not in ("swiglu", "relu2"):
            raise ValueError(f"expert_form={self.expert_form!r}: swiglu or "
                             f"relu2")


def dropless_init_params(cfg: DroplessMoEConfig, key) -> Dict:
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    E, H, D, F = cfg.n_experts, cfg.held, cfg.d_model, cfg.d_ff
    L = cfg.d_expert_io

    def dense(k, fan_in, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(cfg.dtype)

    p = {"router": dense(kr, D, (D, E)), "w1": dense(k1, L, (H, L, F)),
         "w2": dense(k2, F, (H, F, L))}
    if cfg.gated:
        p["w3"] = dense(k3, L, (H, L, F))
    if cfg.scoring == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), cfg.dtype)
    if cfg.d_latent:
        kd, ku = jax.random.split(kr)
        p.update(w_down=dense(kd, D, (D, L)), w_up=dense(ku, L, (L, D)))
    if cfg.d_shared:
        s1, s3, s2, sg = jax.random.split(ks, 4)
        p.update(shared_w1=dense(s1, D, (D, cfg.d_shared)),
                 shared_w2=dense(s2, cfg.d_shared, (cfg.d_shared, D)))
        if cfg.gated:
            p["shared_w3"] = dense(s3, D, (D, cfg.d_shared))
        if cfg.shared_gate:
            p["shared_gate"] = dense(sg, D, (D,))
    return p


def dropless_route(x, router_w, cfg: DroplessMoEConfig, bias=None):
    """``(ids [S, top_k], weights [S, top_k] float32)`` over ALL
    ``n_experts``, in float32 (the product at ``HIGHEST`` precision: which
    expert is last among the chosen hangs on it).  ``softmax`` scoring: the
    ``top_k`` largest probabilities, renormalised to sum to 1.  ``sigmoid``
    scoring: the ``top_k`` largest of ``s + bias`` (the layer's
    ``router_bias``), weighed by their ``s`` over its sum: the bias chooses
    and does not weigh.  The weights times ``routed_scale``."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if cfg.scoring == "softmax":
        top, ids = lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, ids = lax.top_k(scores + bias.astype(jnp.float32), cfg.top_k)
        top = jnp.take_along_axis(scores, ids, axis=-1)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
    return ids, (weights if cfg.routed_scale == 1.0
                 else weights * cfg.routed_scale)


# a block of the sorted assignments is this many times the rows that even
# routing sends to the held experts (``dropless_blocks``)
BLOCK_OVER_EXPECTED = 1.5


def dropless_blocks(rows, cfg: DroplessMoEConfig) -> int:
    """In how many equal blocks the ``rows`` sorted assignments are taken:
    as many as leave a block ``BLOCK_OVER_EXPECTED`` times the rows that
    even routing sends to the held experts, among the divisors of ``rows``.
    One block where every expert is held; five at an eighth of 163840 rows,
    sixteen at a thirty-second of 180224."""
    most = max(1, int(cfg.n_experts / (BLOCK_OVER_EXPECTED * cfg.held)))
    return max(n for n in range(1, most + 1) if rows % n == 0)


def live_blocks(held_counts, rows, cfg: DroplessMoEConfig):
    """How many of :func:`dropless_blocks`' blocks a call computes, from
    the counts it returns (``[..., experts_held]``, a call a row): those
    that begin before the last held row."""
    block = rows // dropless_blocks(rows, cfg)
    return (held_counts.sum(axis=-1) + block - 1) // block


def _expert_products(rows, gate, here, counts, params, cfg):
    """The held experts' part for a run of sorted assignments: ``rows [R,
    d_expert_io]`` of which ``counts [experts_held]`` lead, expert by
    expert, ``gate [R]`` the router's weights, ``here [R, 1]`` which rows
    are held at all."""
    def grouped(lhs, rhs):
        """The held experts' groups of rows times their matrices.  Rows
        past the groups belong to other shares, and the grouped product
        leaves them as they were in both passes (uninitialised memory, NaN
        at times): they are zeroed where they go in and where they come
        out, and so is their gradient."""
        lhs = jnp.where(here, lhs, 0)
        return jnp.where(here, lax.ragged_dot(lhs, rhs, counts), 0)

    if cfg.gated:
        hidden = jax.nn.silu(grouped(rows, params["w1"])) * grouped(
            rows, params["w3"])
        # the router's weight on the narrow side of the down projection
        hidden = (hidden.astype(jnp.float32) * gate[:, None]).astype(
            rows.dtype)
    else:
        hidden = jax.nn.relu(grouped(rows, params["w1"])).astype(jnp.float32)
        hidden = (hidden * hidden * gate[:, None]).astype(rows.dtype)
    return grouped(hidden, params["w2"])


def _block(b, rows, top_k, order, gate, held_counts):
    """``(lo, tokens, gate, counts, here)`` of block ``b`` of ``rows``
    sorted assignments: where it begins, each row's token, its router
    weight, the part of each held expert's group that lies in the block,
    and which of its rows are held at all."""
    lo = b * rows
    ends = jnp.cumsum(held_counts)
    starts = ends - held_counts
    counts = jnp.clip(jnp.minimum(ends, lo + rows) - jnp.maximum(starts, lo),
                      0)
    return (lo, lax.dynamic_slice(order, (lo,), (rows,)) // top_k,
            lax.dynamic_slice(gate, (lo,), (rows,)), counts,
            (lo + jnp.arange(rows) < ends[-1])[:, None])


def _take_rows(x, tokens):
    """``x [S, D]`` at ``tokens [R]``: a block's rows.  Its transpose is
    :func:`_add_rows`; the block loop of :func:`_blocked_experts` writes
    both passes itself, so that each is the other's backward pass."""
    return x.at[tokens].get(mode="promise_in_bounds")


def _add_rows(acc, rows, tokens):
    """``rows [R, D]`` added into the float32 ``acc [S, D]`` at ``tokens
    [R]`` (:func:`_take_rows`' transpose)."""
    return acc.at[tokens].add(rows.astype(acc.dtype),
                              mode="promise_in_bounds")


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _blocked_experts(cfg, blocks, z, order, gate, held_counts, w):
    """The held experts' part ``[S, d_expert_io]`` for tokens ``z``: the
    sorted assignments in ``blocks`` equal blocks, one after the other,
    as many as :func:`live_blocks` says (a loop of that many trips: a block
    that begins past the last held row costs nothing in either pass).  A
    block gathers its own rows (``moe/dispatch``), runs
    :func:`_expert_products` on them (``moe/experts``) and adds its result
    at its rows' tokens into a float32 sum (``moe/combine``) that is
    rounded once.  The backward pass walks the same blocks: it gathers a
    block's rows and its cotangent's, recomputes and differentiates the
    block's products, adds the rows' gradient at their tokens and carries
    the matrices' gradients through the live blocks."""
    S, K, R = z.shape[0], cfg.top_k, order.shape[0] // blocks

    def block(b, acc):
        with jax.named_scope("moe/dispatch"):
            _, tokens, gate_b, counts, here = _block(b, R, K, order, gate,
                                                     held_counts)
            rows = _take_rows(z, tokens)
        with jax.named_scope("moe/experts"):
            out = _expert_products(rows, gate_b, here, counts, w, cfg)
        with jax.named_scope("moe/combine"):
            return _add_rows(acc, out, tokens)

    return lax.fori_loop(
        0, live_blocks(held_counts, order.shape[0], cfg), block,
        jnp.zeros((S, z.shape[1]), jnp.float32)).astype(z.dtype)


def _blocked_experts_fwd(cfg, blocks, z, order, gate, held_counts, w):
    return (_blocked_experts(cfg, blocks, z, order, gate, held_counts, w),
            (z, order, gate, held_counts, w))


def _blocked_experts_bwd(cfg, blocks, res, ct):
    z, order, gate, held_counts, w = res
    K, R = cfg.top_k, order.shape[0] // blocks

    def block(b, carry):
        d_z, d_gate, d_w = carry
        with jax.named_scope("moe/dispatch"):
            lo, tokens, gate_b, counts, here = _block(b, R, K, order, gate,
                                                      held_counts)
            rows = _take_rows(z, tokens)
        with jax.named_scope("moe/combine"):
            ct_b = _take_rows(ct, tokens)
        with jax.named_scope("moe/experts"):
            _, back = jax.vjp(
                lambda r, g, w_: _expert_products(r, g, here, counts, w_,
                                                  cfg), rows, gate_b, w)
            d_rows, d_gate_b, d_w_b = back(ct_b)
            d_w = jax.tree_util.tree_map(jnp.add, d_w, d_w_b)
        with jax.named_scope("moe/dispatch"):
            d_z = _add_rows(d_z, d_rows, tokens)
        return d_z, lax.dynamic_update_slice(d_gate, d_gate_b, (lo,)), d_w

    d_z, d_gate, d_w = lax.fori_loop(
        0, live_blocks(held_counts, order.shape[0], cfg), block,
        (jnp.zeros(z.shape, jnp.float32), jnp.zeros_like(gate),
         jax.tree_util.tree_map(jnp.zeros_like, w)))
    return d_z.astype(z.dtype), None, d_gate, None, d_w


_blocked_experts.defvjp(_blocked_experts_fwd, _blocked_experts_bwd)


def dropless_moe_ffn(x, params, cfg: DroplessMoEConfig, routed=None):
    """The share's part of the layer for tokens ``x [S, D]``.  ``routed``
    is :func:`dropless_route`'s result where the caller has it already (a
    family that moves the selection bias by the load of ALL experts counts
    the ids itself); otherwise the layer routes.

    Returns ``(y [S, D], held_counts [experts_held] int32)``: ``y`` is the
    routed part of the experts held here plus the shared expert (where
    ``d_shared``), ``held_counts`` the assignments that landed on each held
    expert.  Nothing is dropped for any routing: the ``S * top_k``
    assignments are sorted by expert, the held ones first, and taken in
    equal blocks (:func:`dropless_blocks`: a block a little more than even
    routing sends here).  A block gathers its own rows, computes the part
    of the held experts' groups that lies in it (``lax.ragged_dot``) and
    adds its result at its rows' tokens (:func:`_blocked_experts`); the
    blocks past the last held row are not walked, in either pass
    (:func:`live_blocks`).  So the layer costs what the held rows cost:
    one block with the usual load, every block with every assignment here,
    none with none — static shapes, and the result is exact between them.
    A token's sum is float32, rounded once.  With a latent (``d_latent``)
    the rows are the latent's, under the scope ``moe/latent`` with the
    projection back.
    """
    H = cfg.held
    with jax.named_scope("moe/route"):
        ids, weights = routed or dropless_route(
            x, params["router"], cfg, params.get("router_bias"))
        local = ids.reshape(-1) - cfg.first_expert          # [S * K]
        # held assignments first, by expert; the others after every group
        keys = jnp.where((local >= 0) & (local < H), local, H)
        order = jnp.argsort(keys, stable=True)
        held_counts = jnp.sum(
            keys[:, None] == jnp.arange(H, dtype=keys.dtype)[None, :],
            axis=0, dtype=jnp.int32)
        gate = weights.reshape(-1)[order]

    z = x
    if cfg.d_latent:
        with jax.named_scope("moe/latent"):
            z = x @ params["w_down"]
    blocks = dropless_blocks(order.shape[0], cfg)
    trace.expert_blocks["sites"] += 1
    trace.expert_blocks["blocks"] += blocks
    trace.expert_blocks["block_rows"] += order.shape[0] // blocks
    y = _blocked_experts(
        cfg, blocks, z, order, gate, held_counts,
        {k: params[k] for k in ("w1", "w2", "w3") if k in params})
    if cfg.d_latent:
        with jax.named_scope("moe/latent"):
            y = y @ params["w_up"]
    if cfg.d_shared:
        with jax.named_scope("moe/shared"):
            if cfg.gated:
                hidden = jax.nn.silu(x @ params["shared_w1"]) * (
                    x @ params["shared_w3"])
            else:
                hidden = jnp.square(jax.nn.relu(x @ params["shared_w1"]))
            if not cfg.shared_gate:
                return y + hidden @ params["shared_w2"], held_counts
            # One logit a token decides a whole row: it and the row it
            # weighs stay float32 until they are multiplied (rounded to
            # the storage type first, they are most of what the gate's own
            # gradient, a sum that all but cancels, is off by).
            gate = jax.nn.sigmoid(jnp.dot(
                x, params["shared_gate"],
                preferred_element_type=jnp.float32))[:, None]
            y = y + (gate * jnp.dot(
                hidden, params["shared_w2"],
                preferred_element_type=jnp.float32)).astype(x.dtype)
    return y, held_counts


# ----------------------------------------------------------- tiny LM model
@dataclasses.dataclass(frozen=True)
class MoELMConfig:
    """Minimal MoE language model (embed → N × [attention-free mixer +
    MoE FFN] → head) — the test vehicle for expert parallelism."""
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 2
    moe: MoEConfig = dataclasses.field(default_factory=MoEConfig)
    aux_weight: float = 0.01
    dp_axis: Optional[str] = "dp"


def lm_init(cfg: MoELMConfig, key) -> Dict:
    keys = jax.random.split(key, 2 + cfg.n_layers)
    D = cfg.d_model
    return {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, D),
                                    jnp.float32) / np.sqrt(D)).astype(
            cfg.moe.dtype),
        "layers": [init_params(cfg.moe, keys[1 + i])
                   for i in range(cfg.n_layers)],
        "head": (jax.random.normal(keys[-1], (D, cfg.vocab_size),
                                   jnp.float32) / np.sqrt(D)).astype(
            cfg.moe.dtype),
    }


def lm_param_specs(cfg: MoELMConfig) -> Dict:
    return {"embed": P(), "head": P(),
            "layers": [param_specs(cfg.moe) for _ in range(cfg.n_layers)]}


def lm_loss(params, tokens, targets, cfg: MoELMConfig,
            rng: Optional[jax.Array] = None):
    """Per-rank partial mean loss (same sum-semantics convention as
    models/llama.py): scaled so psum over dp AND ep recovers the global
    mean — ep is a DATA split here (GShard-style: every (dp, ep)
    coordinate routes its own token shard; only experts live on ep).

    ``rng`` threads router jitter (cfg.moe.router_noise): folded per
    layer AND per data-axis coordinate, so every (dp, ep) rank draws
    independent noise over its own token shard while redundant compute
    (none here) would stay deterministic.
    """
    B, T = tokens.shape
    x = params["embed"][tokens].reshape(B * T, -1)
    if rng is not None:
        for ax in (cfg.dp_axis, cfg.moe.ep_axis):
            if ax:
                rng = jax.random.fold_in(rng, lax.axis_index(ax))
    aux_total = 0.0
    z_total = 0.0
    for i, lp in enumerate(params["layers"]):
        layer_rng = (jax.random.fold_in(rng, i)
                     if rng is not None else None)
        y, aux, zl = moe_ffn(x, lp, cfg.moe, rng=layer_rng)
        x = x + y
        aux_total = aux_total + aux
        z_total = z_total + zl
    logits = (x @ params["head"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets.reshape(-1)[:, None],
                               axis=-1)[:, 0]
    denom = float(nll.size)
    for ax in (cfg.dp_axis, cfg.moe.ep_axis):
        if ax:
            denom = denom * compat_axis_size(ax)
    router_losses = (cfg.aux_weight * aux_total
                     + cfg.moe.router_z_weight * z_total)
    return (jnp.sum(nll) + router_losses * float(nll.size)) / denom


def lm_sync_grads(grads, cfg: MoELMConfig):
    """psum over dp for everything; over ep only for ep-REPLICATED leaves
    (router/embed/head) — expert slabs are exact per rank (each rank
    computed its own experts' full gradient)."""
    specs = lm_param_specs(cfg)

    def leaf(g, spec):
        if cfg.dp_axis:
            g = lax.psum(g, cfg.dp_axis)
        ep = cfg.moe.ep_axis
        if ep and all(s != ep for s in spec):
            g = lax.psum(g, ep)
        return g

    return jax.tree_util.tree_map(leaf, grads, specs,
                                  is_leaf=lambda s: isinstance(s, P))


def make_train_step(cfg: MoELMConfig, optimizer, with_rng: bool = False):
    """Train step; ``with_rng=True`` adds a trailing ``rng`` argument that
    threads router jitter into ``lm_loss`` (required when
    cfg.moe.router_noise > 0)."""
    import optax

    def _step(params, opt_state, tokens, targets, rng):
        loss_p, grads = jax.value_and_grad(lm_loss)(params, tokens,
                                                    targets, cfg, rng)
        grads = lm_sync_grads(grads, cfg)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        for ax in (cfg.dp_axis, cfg.moe.ep_axis):
            if ax:
                loss_p = lax.psum(loss_p, ax)
        return params, opt_state, loss_p

    if with_rng:
        return _step

    def step(params, opt_state, tokens, targets):
        return _step(params, opt_state, tokens, targets, None)

    return step
