"""The transcript model: configuration, layer mathematics, step program.

Three published families share the step program's frame, the paged
cache and the expert layer; ``LmConfig.model_type`` chooses the block.

**``afmoe``.** Every layer is
``h = h + N2(Attn(N1(h)))`` then ``h = h + N4(Mlp(N3(h)))`` (RMSNorm
before and after each block). Attention has 8 query heads a K/V head,
an RMSNorm over each head's dims of ``q`` and ``k``, rotary embedding on
``sliding_attention`` layers only (``full_attention`` layers carry no
positions), a band of ``sliding_window`` keys on the window layers, and
an output gate ``sigmoid(x Wg)`` on the concatenated heads. The first
``num_dense_layers`` MLPs are SwiGLU; the rest are the expert layer of
``moe.py``. The embedding is scaled by ``sqrt(hidden)`` (``mup_enabled``)
and the head is untied.

**``KeyeVL2``** (the language model; the vision tower is not built).
Every layer is ``h = h + Attn(N1(h)) Wo`` then ``h = h + Moe(N2(h))``
(two RMSNorms, no gate, no dense layer, no shared expert, softmax
routing), rotary embedding on every layer, and a learned sparse
attention: an indexer of ``index_heads`` heads of ``index_head_dim``
dims scores every causal key (``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
kI[s])``, float32), each query keeps its ``index_topk`` best keys (all
of them while it has no more; ties to the lower position) and attends
over those alone, one choice for all heads. The indexer's keys live in
a second pool beside K and V on the full class's page numbers.
:func:`index_scores` computes the scores over a sequence's pages,
:func:`select_keys` finds each query's k-th best score digit by digit
over the float32 bits (exact; the scores are read, never sorted; on a
TPU a chunk's choice is one kernel that reads a tile's scores once into
VMEM and searches them there, ``attention_kernel.py::
select_keys_kernel``, chosen by :func:`select_form`) and
:func:`top_positions` is ``lax.top_k`` for the few decoding rows. Two
forms of the attention compute the same thing: :func:`paged_attention`
masked by the choice (a prefill chunk: 2,048 queries share the pages
they read) and :func:`gathered_attention` over the chosen keys alone (a
decoding row: ``index_topk`` keys of K and V in place of its whole
context).

**``xing4_0``.** Latent attention (MLA as DeepSeek-V2/V3 publish it)
under a four-stream residual. ``x`` the sublayer's normed input:
``c_q = RMSNorm(x Wqa)``, ``q = c_q Wqb`` as heads of ``[nope | rope]``;
``[c_kv | k_r] = x Wkva``, ``c = RMSNorm(c_kv)``, ``k_rope = rot(k_r)``
ONE head for all; ``[k_nope_h | v_h] = c Wkvb[h]``; ``s = (q_nope .
k_nope + q_rope . k_rope) * scale``. The cache holds ``(c, k_rope)``,
ONE array a layer of ``kv_lora_rank + qk_rope_head_dim`` numbers a
position on the full class's page numbers. Two forms of that sum,
chosen by shape as :func:`attention_form` chooses: the ROWS (one query a
sequence) run it **absorbed** (``q_lat = q_nope Wuk^T``, scores against
the latent itself, ``o = (softmax(s) c) Wuv``: the 32 heads read ONE key
head of 576 whose first 512 lanes are also its value, so a latent is
read once); a prefill CHUNK runs it **expanded** (a block of the
context's latents through ``Wkvb`` once for all the chunk's queries,
then heads of 192 / 128). Rotary is YaRN (:func:`yarn_inv_freq`). The
residual state of a token is ``X`` (streams, hidden), the embedding
copied ``hc_mult`` times; around EACH sublayer ``F`` the
manifold-constrained hyper-connection (:func:`hc_maps`): ``u = H_pre X``,
``X' = H_res X + H_post^T F(u)`` with ``H_res`` made doubly stochastic by
``hc_sinkhorn_iters`` Sinkhorn iterations; the streams are summed before
the final norm. Experts are ``afmoe``'s (sigmoid, a selection bias, a
shared expert). The family's next-token-prediction module is not built.

**``qwen3_next``.** Three Gated DeltaNet layers to every gated full
attention layer (``layer_types`` ``linear_attention`` /
``full_attention``); every layer ``h = h + Mix(N1(h))`` then ``h = h +
Moe(N2(h))``, every norm but the DeltaNet's output norm zero-centred
(``x / rms(x) * (1 + w)``). A DeltaNet layer (:func:`_gdn_mixer`):
``[q k v z] = x Wqkvz`` grouped by key head, ``[b a] = x Wba``, a causal
depthwise conv of ``linear_conv`` taps then SiLU over ``[q k v]``, q and
k L2-normalised (q times ``dk^-0.5``), the key heads repeated to the
value heads, ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
dt_bias)``, and per value head the gated delta rule on a float32 state
``S`` (dk x dv): ``S <- e^g S``, ``S <- S + k ((v - S^T k) beta)^T``,
``o = S^T q``; then ``RMSNorm(o) w SiLU(z)`` and ``Wout``. A request's
``S`` and its conv tail (the last ``linear_conv - 1`` inputs) live in a
slot of their own (``cache.py``), not in pages. Two forms of the one
recurrence: a prefill chunk runs it chunkwise (:func:`gdn_chunk`, the
WY form over sub-chunks of ``GDN_SUB``, the state carried across them),
the rows one token each (:func:`gdn_rows`); both are XLA. A full layer
(:func:`_gated_attention`) is grouped-query attention over K/V pages
with heads of 256: ``[q | gate]`` a head from ``Wq``, zero-centred
``q_norm`` / ``k_norm``, rotate-half rotary over the first
``rotary_dim`` dims, ``softmax(q k^T / sqrt(hd)) v * sigmoid(gate)``,
``Wo``. Experts: softmax over ALL ``router_experts`` router outputs,
the top k renormalised; the layer holds ``num_experts`` of them (from
``expert_first`` on: one chip's share of an expert-parallel
deployment), computes only the pairs routed to those and adds
``sigmoid(x Wsg) * Shared(x)``.

Precision as stated: weights, K/V, indexer keys and latents bfloat16,
products accumulate in float32, the residual stream(s), norms, router,
index scores, hyper-connection mappings, softmax and logits float32;
the DeltaNet's state and its recurrence float32 (products at
``HIGHEST``), its conv tail bfloat16.

**The step program** (:func:`build_step`) serves one engine step: at
most one prefill chunk of ONE request (``chunk`` tokens, a static
bucket) beside every decoding row (``rows``, one token each). All of a
step's tokens pass the projections, the dense MLPs and the experts
together; attention runs per sequence over the paged cache: the chunk's
and the rows' new K/V are written into their pages first, then each
query block reads its sequence's pages ``kv_block_pages`` at a time
under an online softmax (never more than queries x block scores; on a
TPU inside one kernel for the chunk and one for the rows,
``attention_kernel.py``, where they never leave the chip and a row
reads its own pages and no other's), from the table's first page to
the page of its last query. A table is a
list of physical pages from logical page ``base // page`` on: for a
full layer ``base`` is 0, for a window layer the host hands in only the
pages the band touches (``cache.py``), so a window layer visits no page
behind its window. Physical page 0 is never handed out: unallocated
table slots and padded rows point there, reads of it are masked.

The next token (greedy) goes back in on the device: ``last_tok`` holds
each row's newest token, the step overwrites it, and a request's last
prefill chunk deposits its first token at the row it will decode in.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from vlog_tpu.lm import attention_kernel, moe

F32 = jnp.float32
BF16 = jnp.bfloat16
SLIDING = "sliding_attention"
FULL = "full_attention"
LINEAR = "linear_attention"
XING = "xing4_0"
QWEN3_NEXT = "qwen3_next"
MASKED = -1e30
GDN_SUB = 64        # positions a sub-chunk of the chunkwise DeltaNet


@dataclass(frozen=True)
class LmConfig:
    """The model's shape, from a published ``config.json`` (HF keys).
    What only one family has is zero, empty or ``False`` for the others."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    layer_types: tuple[str, ...]        # of the layers held here
    num_dense_layers: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_shared_experts: int
    route_norm: bool
    route_scale: float
    sliding_window: int
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    mup_enabled: bool
    model_type: str = "afmoe"
    score_func: str = "sigmoid"         # or softmax
    index_heads: int = 0                # the sparse attention's indexer
    index_head_dim: int = 0
    index_topk: int = 0                 # keys a query attends (0: all)
    q_lora_rank: int = 0                # latent attention (0: K and V)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN: factor, original positions, beta_fast, beta_slow, mscale,
    # mscale_all_dim (empty: plain rotary)
    rope_yarn: tuple[float, ...] = ()
    hc_mult: int = 0                    # residual streams (0: one, plain)
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 0.0
    hc_clamp: tuple[float, float] = (0.0, 0.0)
    rotary_dim: int = 0                 # rotated dims of a head (0: all)
    linear_key_heads: int = 0           # Gated DeltaNet (0: none)
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 0                # the causal conv's taps
    router_experts: int = 0             # the router's width (0: num_experts)
    expert_first: int = 0               # the first expert held here

    @classmethod
    def from_hf(cls, d: dict) -> "LmConfig":
        families = {"afmoe": cls._from_afmoe, "KeyeVL2": cls._from_keye,
                    XING: cls._from_xing, QWEN3_NEXT: cls._from_qwen3_next}
        family = d.get("model_type", "afmoe")
        if family not in families:
            raise ValueError(f"model_type {family!r} is not built (built: "
                             f"{', '.join(families)})")
        return families[family](d)

    @classmethod
    def _from_afmoe(cls, d: dict) -> "LmConfig":
        n = int(d["num_hidden_layers"])
        kinds = tuple(d["layer_types"][:n])
        if len(kinds) != n:
            raise ValueError("layer_types is shorter than num_hidden_layers")
        if d.get("score_func", "sigmoid") != "sigmoid" \
                or int(d.get("n_group", 1)) != 1 \
                or int(d.get("topk_group", 1)) != 1:
            raise ValueError("afmoe: only sigmoid routing in one group is "
                             "built")
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=int(d["num_attention_heads"]),
            num_key_value_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]), layer_types=kinds,
            num_dense_layers=int(d["num_dense_layers"]),
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=int(d["num_experts"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            num_shared_experts=int(d["num_shared_experts"]),
            route_norm=bool(d["route_norm"]),
            route_scale=float(d["route_scale"]),
            sliding_window=int(d["sliding_window"]),
            vocab_size=int(d["vocab_size"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            rope_theta=float(d["rope_theta"]),
            mup_enabled=bool(d.get("mup_enabled", False)))

    @classmethod
    def _from_keye(cls, d: dict) -> "LmConfig":
        """The language model of ``KeyeVL2``: every layer sparse
        attention and experts. What the family can say and this program
        does not run is refused by name."""
        sa = d.get("sa_config") or {}
        refused = {
            "mlp_only_layers": bool(d.get("mlp_only_layers")),
            "decoder_sparse_step != 1":
                int(d.get("decoder_sparse_step", 1)) != 1,
            "use_sliding_window": bool(d.get("use_sliding_window")),
            "attention_bias": bool(d.get("attention_bias")),
            "tie_word_embeddings": bool(d.get("tie_word_embeddings")),
            "sa_config.indexer_num_kv_heads != 1":
                int(sa.get("indexer_num_kv_heads", 1)) != 1,
            "no sa_config.topk": int(sa.get("topk", 0)) < 1}
        bad = [name for name, hit in refused.items() if hit]
        if bad:
            raise ValueError(f"KeyeVL2: not built: {', '.join(bad)}")
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=int(d["num_attention_heads"]),
            num_key_value_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]),
            layer_types=(FULL,) * int(d["num_hidden_layers"]),
            num_dense_layers=0,
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=int(d["num_experts"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            num_shared_experts=0,
            route_norm=bool(d.get("norm_topk_prob", True)),
            route_scale=1.0, sliding_window=0,
            vocab_size=int(d["vocab_size"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            rope_theta=float(d["rope_theta"]), mup_enabled=False,
            model_type="KeyeVL2", score_func="softmax",
            index_heads=int(sa["indexer_num_heads"]),
            index_head_dim=int(sa["indexer_head_dim"]),
            index_topk=int(sa["topk"]))

    @classmethod
    def _from_xing(cls, d: dict) -> "LmConfig":
        """``xing4_0``: latent attention under hyper-connections, two
        dense layers then sigmoid-routed experts beside a shared one.
        ``num_nextn_predict_layers`` is read past: the module that
        drafts the next token sits after the last layer and the main
        model serves without it."""
        scaling = d.get("rope_scaling") or {}
        refused = {
            "n_group != 1": int(d.get("n_group", 1)) != 1,
            "topk_group != 1": int(d.get("topk_group", 1)) != 1,
            "scoring_func other than sigmoid":
                d.get("scoring_func", "sigmoid") != "sigmoid",
            "topk_method other than noaux_tc":
                d.get("topk_method", "noaux_tc") != "noaux_tc",
            "moe_layer_freq != 1": int(d.get("moe_layer_freq", 1)) != 1,
            "attention_bias": bool(d.get("attention_bias")),
            "tie_word_embeddings": bool(d.get("tie_word_embeddings")),
            "rope_scaling.type other than yarn":
                scaling.get("type", scaling.get("rope_type")) != "yarn",
            "hc_mult < 2": int(d.get("hc_mult", 0)) < 2}
        bad = [name for name, hit in refused.items() if hit]
        if bad:
            raise ValueError(f"{XING}: not built: {', '.join(bad)}")
        n = int(d["num_hidden_layers"])
        nope, rope_dim = int(d["qk_nope_head_dim"]), int(d["qk_rope_head_dim"])
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=int(d["num_attention_heads"]),
            # what the cache holds: one latent key head for every query
            num_key_value_heads=1, head_dim=nope + rope_dim,
            layer_types=(FULL,) * n,
            num_dense_layers=min(int(d["first_k_dense_replace"]), n),
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=int(d["n_routed_experts"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            num_shared_experts=int(d.get("n_shared_experts") or 0),
            route_norm=bool(d.get("norm_topk_prob", True)),
            route_scale=float(d.get("routed_scaling_factor", 1.0)),
            sliding_window=0, vocab_size=int(d["vocab_size"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            rope_theta=float(d["rope_theta"]), mup_enabled=False,
            model_type=XING, q_lora_rank=int(d["q_lora_rank"]),
            kv_lora_rank=int(d["kv_lora_rank"]), qk_nope_head_dim=nope,
            qk_rope_head_dim=rope_dim, v_head_dim=int(d["v_head_dim"]),
            rope_yarn=(float(scaling["factor"]),
                       float(scaling["original_max_position_embeddings"]),
                       float(scaling.get("beta_fast", 32)),
                       float(scaling.get("beta_slow", 1)),
                       float(scaling.get("mscale", 1)),
                       float(scaling.get("mscale_all_dim", 0))),
            hc_mult=int(d["hc_mult"]),
            hc_sinkhorn_iters=int(d["hc_sinkhorn_iters"]),
            hc_eps=float(d["hc_eps"]),
            hc_clamp=(float(d["mhc_h_res_clamp_min"]),
                      float(d["mhc_h_res_clamp_max"])))

    @classmethod
    def _from_qwen3_next(cls, d: dict) -> "LmConfig":
        """``qwen3_next``: Gated DeltaNet and gated full attention (the
        layer kinds from ``layer_types``, else every
        ``full_attention_interval``-th layer full), softmax-routed experts
        beside a gated shared expert on every layer. Where this holds a
        share of the experts, ``num_experts`` counts the experts held and
        ``published_num_experts`` the router's outputs, the first held
        being ``first_held_expert``. ``num_nextn_predict_layers`` is read
        past, as for ``xing4_0``."""
        n = int(d["num_hidden_layers"])
        every = int(d.get("full_attention_interval", 4))
        kinds = tuple(d.get("layer_types") or [
            FULL if (i + 1) % every == 0 else LINEAR for i in range(n)])[:n]
        refused = {
            "decoder_sparse_step != 1":
                int(d.get("decoder_sparse_step", 1)) != 1,
            "mlp_only_layers": bool(d.get("mlp_only_layers")),
            "use_sliding_window": bool(d.get("use_sliding_window")),
            "attention_bias": bool(d.get("attention_bias")),
            "tie_word_embeddings": bool(d.get("tie_word_embeddings")),
            "rope_scaling": bool(d.get("rope_scaling")),
            "hidden_act other than silu": d.get("hidden_act", "silu")
            != "silu",
            "layer kinds other than linear_attention and full_attention":
                len(kinds) != n or not set(kinds) <= {LINEAR, FULL}}
        bad = [name for name, hit in refused.items() if hit]
        if bad:
            raise ValueError(f"{QWEN3_NEXT}: not built: {', '.join(bad)}")
        hd = int(d["head_dim"])
        held = int(d["num_experts"])
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=int(d["num_attention_heads"]),
            num_key_value_heads=int(d["num_key_value_heads"]),
            head_dim=hd, layer_types=kinds, num_dense_layers=0,
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=held,
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            num_shared_experts=int(d["shared_expert_intermediate_size"])
            // int(d["moe_intermediate_size"]),
            route_norm=bool(d.get("norm_topk_prob", True)),
            route_scale=1.0, sliding_window=0,
            vocab_size=int(d["vocab_size"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            rope_theta=float(d["rope_theta"]), mup_enabled=False,
            model_type=QWEN3_NEXT, score_func="softmax",
            rotary_dim=int(hd * float(d.get("partial_rotary_factor", 1.0))),
            linear_key_heads=int(d["linear_num_key_heads"]),
            linear_value_heads=int(d["linear_num_value_heads"]),
            linear_key_dim=int(d["linear_key_head_dim"]),
            linear_value_dim=int(d["linear_value_head_dim"]),
            linear_conv=int(d["linear_conv_kernel_dim"]),
            router_experts=int(d.get("published_num_experts", held)),
            expert_first=int(d.get("first_held_expert", 0)))

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def linear_layers(self) -> int:
        return sum(k == LINEAR for k in self.layer_types)

    @property
    def conv_dim(self) -> int:
        """Channels of a DeltaNet layer's conv: ``[q k v]``."""
        return 2 * self.linear_key_heads * self.linear_key_dim \
            + self.linear_value_heads * self.linear_value_dim

    @property
    def held(self) -> tuple[int, int] | None:
        """The router outputs whose experts this holds, ``(first,
        end)``; ``None`` where it holds every one."""
        if not self.router_experts or self.router_experts == self.num_experts:
            return None
        return (self.expert_first, self.expert_first + self.num_experts)

    @property
    def latent_width(self) -> int:
        """Numbers a position's cached latent holds (0: K and V)."""
        return self.kv_lora_rank + self.qk_rope_head_dim \
            if self.kv_lora_rank else 0

    @property
    def window_layers(self) -> int:
        return sum(k == SLIDING for k in self.layer_types)

    @property
    def full_layers(self) -> int:
        return sum(k == FULL for k in self.layer_types)

    def position_bytes(self) -> tuple[int, int]:
        """Cache bytes one position costs over the layers held here, by
        class ``(window, full)``: K and V in bfloat16 (``afmoe``), with
        the indexer's key beside them (``KeyeVL2``), or the one latent
        ``(c, k_rope)`` in their place (``xing4_0``: 1,152 B a layer
        against the others' 2,048)."""
        if self.latent_width:
            return (0, self.full_layers * self.latent_width * 2)
        kv = 2 * self.num_key_value_heads * self.head_dim * 2
        index = self.index_head_dim * 2 if self.index_topk else 0
        return (self.window_layers * kv, self.full_layers * (kv + index))


@dataclass(frozen=True)
class Geometry:
    """How the engine lays a deployment onto the device."""

    rows: int = 32              # decoding rows a step
    chunk: int = 2048           # most prefill tokens a step
    page: int = 256             # positions a page
    context_cap: int = 40_960   # longest prompt + output served
    window_pages: int = 0       # pool size of the window class (page 0 incl.)
    full_pages: int = 0         # pool size of the full class (page 0 incl.)
    kv_block_pages: int = 4     # key pages an attention block reads

    def ring(self, window: int) -> int:
        """Pages a window table holds at most: the window plus one chunk
        (0 for a model without window layers: no table at all)."""
        return (window + self.chunk) // self.page if window else 0

    @property
    def max_pages(self) -> int:
        return -(-self.context_cap // self.page)

    def chunk_buckets(self) -> tuple[int, ...]:
        """0 (decode only), then page, 2 page, ... up to chunk."""
        out, c = [0], self.page
        while c < self.chunk:
            out.append(c)
            c *= 2
        return tuple(out + [self.chunk])

    @property
    def key_width(self) -> int:
        """Positions a sequence's score row holds: the context cap,
        rounded up to whole attention blocks."""
        block = self.kv_block_pages * self.page
        return -(-self.context_cap // block) * block

    def check(self, cfg: LmConfig) -> None:
        if self.chunk % self.page or cfg.sliding_window % self.page:
            raise ValueError("chunk and window must be whole pages")
        if self.context_cap % self.page:
            raise ValueError("context_cap must be whole pages")
        if cfg.window_layers and self.window_pages < 2 \
                or cfg.full_layers and self.full_pages < 2:
            raise ValueError("a layer class needs a pool of page 0 and at "
                             "least one more")
        if not cfg.window_layers and self.window_pages:
            raise ValueError("no window layer: a window pool would hold "
                             "nothing (window_pages must be 0)")
        if cfg.index_topk > self.key_width:
            raise ValueError("index_topk exceeds the context cap")


# --------------------------------------------------------------------------
# layer mathematics
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x: jax.Array, pos: jax.Array, theta: float,
         inv: jax.Array | None = None) -> jax.Array:
    """Rotate-half rotary embedding over the whole head: ``x`` (T, heads,
    hd) float32, ``pos`` (T,) absolute positions; ``inv`` (hd / 2,) the
    frequencies where they are not ``theta``'s plain ones."""
    hd = x.shape[-1]
    if inv is None:
        inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) \
        * jnp.sin(ang)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: float,
                  beta_fast: float, beta_slow: float) -> jax.Array:
    """YaRN's ``dim / 2`` frequencies: the plain ones ``f`` where a
    dimension turns more than ``beta_fast`` times over the ``original``
    positions, ``f / factor`` where it turns fewer than ``beta_slow``
    times, a linear ramp between (the published
    ``yarn_find_correction_range`` / ``yarn_linear_ramp_mask``)."""
    def turns_at(beta: float) -> float:
        return dim * math.log(original / (beta * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    f = theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    m = 1.0 - ramp
    return f / factor * (1.0 - m) + f * m


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def latent_scale(cfg: LmConfig) -> float:
    """The softmax scale of latent attention: ``head_dim ** -0.5`` times
    YaRN's ``mscale(factor, mscale_all_dim) ** 2``."""
    factor, _orig, _fast, _slow, _m, m_all = cfg.rope_yarn
    s = yarn_mscale(factor, m_all) if m_all else 1.0
    return cfg.head_dim ** -0.5 * s * s


def latent_rope(cfg: LmConfig, x: jax.Array, pos: jax.Array) -> jax.Array:
    """YaRN rotary over the rope dims of a latent-attention head."""
    factor, orig, fast, slow, m, m_all = cfg.rope_yarn
    inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, factor, orig,
                        fast, slow)
    out = rope(x, pos, cfg.rope_theta, inv)
    # cos and sin carry mscale / mscale_all_dim (1 where they are equal)
    return out * (yarn_mscale(factor, m) / yarn_mscale(factor, m_all)) \
        if m_all and m != m_all else out


def sinkhorn(logits: jax.Array, iters: int, eps: float) -> jax.Array:
    """``exp(logits)`` (n, n, T) made doubly stochastic: ``iters`` times
    each row over its sum, then each column over its sum. Every
    iteration is run, converged or not. The leading two axes are the
    matrix and the tokens lie in the lanes; the sums over an axis of
    ``n`` are written out as adds of slices, so an iteration is
    elementwise and one pass (a reduction would end the fusion)."""
    n = logits.shape[0]

    def body(_, m):
        m = m / (sum(m[:, j] for j in range(n))[:, None] + eps)
        return m / (sum(m[i] for i in range(n))[None] + eps)

    return lax.fori_loop(0, iters, body, jnp.exp(logits))


def hc_maps(cfg: LmConfig, xs: jax.Array, proj: jax.Array, bias: jax.Array,
            alpha: jax.Array):
    """The three mappings of one hyper-connection from the residual
    state ``xs`` (T, n, H) float32: ``z = vec(X) / sqrt(mean(vec(X)^2) +
    hc_eps)``, ``[Hp | Ho | Hr] = alpha * (z proj) + bias`` (``proj`` (n H,
    2n + n^2), ``Hr`` row-major), ``H_pre = sigmoid(Hp)`` (n, T), ``H_post
    = 2 sigmoid(Ho)`` (n, T), ``H_res = sinkhorn(clamp(Hr))`` (n, n, T).
    All float32; also returns the largest distance of a row or column
    sum of each token's ``H_res`` from 1 (T,)."""
    t, n, h = xs.shape
    flat = xs.reshape(t, n * h)
    # the norm is one number a token: applied to the 24 products, not to
    # the 14,336 inputs
    z = jnp.dot(flat, proj.astype(F32), precision=lax.Precision.HIGHEST,
                preferred_element_type=F32) * lax.rsqrt(
                    jnp.mean(flat * flat, axis=-1, keepdims=True)
                    + cfg.hc_eps)
    z = z.T                                 # (24, T): tokens in the lanes
    pre = jax.nn.sigmoid(alpha[0] * z[:n] + bias[:n, None])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[n:2 * n] + bias[n:2 * n, None])
    logits = (alpha[2] * z[2 * n:] + bias[2 * n:, None]).reshape(n, n, t)
    res = sinkhorn(jnp.clip(logits, *cfg.hc_clamp), cfg.hc_sinkhorn_iters,
                   cfg.hc_eps)
    defect = jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0), axis=0),
                         jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0), axis=0))
    return pre, post, res, defect


def hyper_connect(cfg: LmConfig, xs: jax.Array, hc: tuple, fn):
    """One sublayer ``fn`` under its hyper-connection: ``xs`` (T, n, H)
    -> ``(H_res X + H_post^T fn(H_pre X), defect (T,))``; ``hc`` the
    connection's ``(proj, bias, alpha)``."""
    with jax.named_scope("lm.hc.map"):
        pre, post, res, defect = hc_maps(cfg, xs, *hc)
    # sums over the n streams written out: one elementwise pass each,
    # where a contraction over 4 would be handed to the matrix unit
    n = xs.shape[1]
    streams = [xs[:, j, :] for j in range(n)]
    with jax.named_scope("lm.hc.pre"):
        u = sum(pre[j][:, None] * streams[j] for j in range(n))
    y = fn(u)
    with jax.named_scope("lm.hc.post"):
        xs = jnp.stack([
            sum(res[i, j][:, None] * streams[j] for j in range(n))
            + post[i][:, None] * y for i in range(n)], axis=1)
    return xs, defect


def mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x.astype(BF16), w, preferred_element_type=F32)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float
               ) -> jax.Array:
    x = x.astype(F32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def attention_form(seqs: int, nq: int, nkv: int, g: int, hd: int, page: int,
                   *, chosen: bool = False, expand: bool = False) -> str:
    """The form :func:`paged_attention` takes for ``seqs`` sequences of
    ``nq`` queries each, ``g`` query heads a kv head: ``"kernel"`` (a
    prefill chunk: one sequence, with or without a choice of keys),
    ``"rows_kernel"`` (rows of one query each over K/V pages, no choice)
    or ``"loop"`` (any other backend than a TPU, a pool that ``expand``
    reads, shapes Mosaic does not tile)."""
    if expand or jax.default_backend() != "tpu":
        return "loop"
    if seqs == 1 and nq > 1 and attention_kernel.supported(nq, nkv, hd, page):
        return "kernel"
    if nq == 1 and not chosen and attention_kernel.rows_supported(
            nkv, g, hd, page):
        return "rows_kernel"
    return "loop"


def paged_attention(q: jax.Array, qpos: jax.Array, last_pos: jax.Array,
                    pool_k: jax.Array, pool_v: jax.Array, table: jax.Array,
                    base: jax.Array, *, window: int | None, page: int,
                    block_pages: int, chosen: jax.Array | None = None,
                    expand=None, value_width: int | None = None,
                    keys_minor: bool = False
                    ) -> tuple[jax.Array, jax.Array]:
    """Online-softmax attention of a batch of sequences over their pages.

    ``q`` (S, Q, nkv, g, hd) bfloat16, already scaled; ``qpos`` (S, Q)
    absolute positions of the queries; ``last_pos`` (S,) the last
    position that holds a key (-1: the sequence is absent); ``table``
    (S, W) physical pages from position ``base`` (S,) on; ``chosen``
    (S, Q, keys) bool or int8 (non-zero: chosen), where given, the keys
    each query attends (by position from ``base`` on; whole blocks
    wide). Returns
    ``(out (S, Q, nkv, g, hd) float32, pages visited (S,))``.

    Where the pool holds something other than K beside V (a latent),
    ``pool_v`` is ``None`` and ``expand`` turns a gathered block of
    ``pool_k`` ``(S, block_pages, ...)`` into that block's ``(k (S, keys, nkv,
    hd), v (S, keys, nkv, value_width))``: the value may be a slice of
    the key (read once) or both a product of the block; ``out`` is then
    ``value_width`` wide. With ``keys_minor`` the pool holds ONE K/V
    head with the positions in the lanes, ``(pages, hd, page)``, and
    ``expand`` returns ``(k (S, block_pages, hd, page), v (S,
    block_pages, value_width, page))``: a gathered block is then used
    as it lies, page by page (a TPU lays a ``(pages, page, 576)`` array
    out with the 256 in the lanes whatever the program says, and every
    use of it the other way round relays 880 MB out: 10 ms a layer, my
    chip run, PR 35). Such a call runs the loop.

    Three forms of the one algorithm, chosen by :func:`attention_form`
    from the call's shapes and the backend. On a TPU a prefill chunk
    (one sequence of ``Q`` consecutive positions from ``qpos[0, 0]`` on)
    runs as one kernel that keeps a block's scores on the chip, and rows
    of one query each as one kernel in which a row reads its OWN pages,
    so a step's work is the sum of the rows' contexts (both in
    ``attention_kernel.py``). Everything else runs the loop below, every
    row as long as the longest: any other backend, ``expand`` and
    ``keys_minor`` callers, shapes Mosaic does not tile.
    """
    s, nq, nkv, g, hd = q.shape
    width = table.shape[1]
    keys = block_pages * page
    n_pages = jnp.where(last_pos >= 0, (last_pos - base) // page + 1, 0)
    vd = hd if expand is None else value_width
    form = attention_form(s, nq, nkv, g, hd, page, chosen=chosen is not None,
                          expand=expand is not None)
    if form == "kernel":
        out = attention_kernel.chunk_attention(
            q[0], qpos[0, 0], n_pages[0], pool_k, pool_v, table[0], base[0],
            window=window, page=page, block_pages=block_pages,
            chosen=None if chosen is None else chosen[0])
        return out[None], n_pages
    if form == "rows_kernel":
        out = attention_kernel.rows_attention(
            q[:, 0], qpos[:, 0], last_pos, pool_k, pool_v, table, base,
            window=window, page=page, block_pages=block_pages)
        return out[:, None], n_pages
    n_blocks = (jnp.max(n_pages) + block_pages - 1) // block_pages
    lane = jnp.arange(keys, dtype=jnp.int32)

    def body(i, carry):
        m, l, acc = carry
        slots = i * block_pages + jnp.arange(block_pages, dtype=jnp.int32)
        live = slots[None, :] < n_pages[:, None]              # (S, bp)
        phys = jnp.where(live, jnp.take(table, jnp.minimum(slots, width - 1),
                                        axis=1), 0)
        if expand is None:
            k = pool_k[phys].reshape(s, keys, nkv, hd)
            v = pool_v[phys].reshape(s, keys, nkv, hd)
        else:                           # the block as the pool holds it
            k, v = expand(pool_k[phys])
        kpos = base[:, None] + i * keys + lane[None, :]       # (S, K)
        ok = kpos[:, None, :] <= qpos[:, :, None]             # (S, Q, K)
        if window is not None:
            ok &= kpos[:, None, :] > qpos[:, :, None] - window
        ok &= jnp.repeat(live, page, axis=1)[:, None, :]
        if chosen is not None:
            ok &= lax.dynamic_slice_in_dim(chosen, i * keys, keys,
                                           axis=2).astype(bool)
        if keys_minor:
            # one K/V head: k (S, bp, hd, page) enters the product as the
            # gather left it
            sc = jnp.einsum("sqgd,sbdk->sgqbk", q[:, :, 0], k,
                            preferred_element_type=F32).reshape(
                                s, 1, g, nq, keys)
        else:
            sc = jnp.einsum("sqngd,sknd->sngqk", q, k,
                            preferred_element_type=F32)
        sc = jnp.where(ok[:, None, None, :, :], sc, MASKED)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.where(ok[:, None, None, :, :],
                      jnp.exp(sc - m_new[..., None]), 0.0)
        scale = jnp.exp(m - m_new)
        l = l * scale + jnp.sum(p, axis=-1)
        if keys_minor:
            pv = jnp.einsum(
                "sgqbk,sbdk->sgqd", p[:, 0].astype(BF16).reshape(
                    s, g, nq, block_pages, page), v,
                preferred_element_type=F32)[:, None]
        else:
            pv = jnp.einsum("sngqk,sknd->sngqd", p.astype(BF16), v,
                            preferred_element_type=F32)
        return m_new, l, acc * scale[..., None] + pv

    m0 = jnp.full((s, nkv, g, nq), MASKED, F32)
    l0 = jnp.zeros((s, nkv, g, nq), F32)
    a0 = jnp.zeros((s, nkv, g, nq, vd), F32)
    _, l, acc = lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4), n_pages


# --------------------------------------------------------------------------
# learned sparse attention: index scores, the choice, attention over it
# --------------------------------------------------------------------------

def index_scores(qi: jax.Array, wi: jax.Array, qpos: jax.Array,
                 last_pos: jax.Array, pool_ki: jax.Array, table: jax.Array,
                 *, page: int, block_pages: int, width: int) -> jax.Array:
    """The indexer's score of every causal key, for a batch of
    sequences over their pages.

    ``qi`` (S, Q, J, D) bfloat16 (rotated), ``wi`` (S, Q, J) float32
    (scaled), ``qpos`` (S, Q), ``last_pos`` (S,) as for
    :func:`paged_attention`, ``pool_ki`` (pages, page, D) bfloat16,
    ``table`` (S, W) from position 0 on. Returns ``(S, Q, width)``
    float32: ``sum_j wi[j] * relu(qi[j] . ki[s])`` at key positions
    ``s <= qpos`` that hold a key, ``-inf`` elsewhere; never ``-0.0``.
    """
    s, nq, _heads, dim = qi.shape
    keys = block_pages * page
    n_pages = jnp.where(last_pos >= 0, last_pos // page + 1, 0)
    n_blocks = (jnp.max(n_pages) + block_pages - 1) // block_pages
    lane = jnp.arange(keys, dtype=jnp.int32)
    wt = wi.transpose(0, 2, 1)[..., None]                     # (S, J, Q, 1)

    def body(i, out):
        slots = i * block_pages + jnp.arange(block_pages, dtype=jnp.int32)
        live = slots[None, :] < n_pages[:, None]
        phys = jnp.where(live, jnp.take(
            table, jnp.minimum(slots, table.shape[1] - 1), axis=1), 0)
        ki = pool_ki[phys].reshape(s, keys, dim)
        dots = jnp.einsum("sqjd,skd->sjqk", qi, ki,
                          preferred_element_type=F32)
        score = jnp.sum(jnp.maximum(dots, 0.0) * wt, axis=1)  # (S, Q, K)
        score = jnp.where(score == 0.0, 0.0, score)           # no -0.0
        kpos = i * keys + lane
        ok = (kpos[None, None, :] <= qpos[:, :, None]) \
            & jnp.repeat(live, page, axis=1)[:, None, :]
        return lax.dynamic_update_slice_in_dim(
            out, jnp.where(ok, score, -jnp.inf), i * keys, axis=2)

    return lax.fori_loop(0, n_blocks, body,
                         jnp.full((s, nq, width), -jnp.inf, F32))


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


RADIX_BITS = 2      # bits of the k-th score that one pass settles


def select_keys(scores: jax.Array, top: int, n_keys: jax.Array, *,
                block: int) -> jax.Array:
    """Each query's ``top`` best-scored keys as a mask, exactly, with no
    sort: ``scores`` (S, Q, W) float32 with ``-inf`` at what is no key
    (:func:`index_scores`), ``n_keys`` () how many leading columns hold
    any key (the rest are not read). Returns (S, Q, W) bool: every key
    of a query that has at most ``top``; else the ``top`` of largest
    score, ties to the lower position (``lax.top_k``'s set).

    The k-th largest score of a row is found digit by digit over the 32
    bits of its ordered image, ``RADIX_BITS`` a pass: a pass counts, for
    each value of the next digit, the scores that reach the candidate
    (one read of the first ``n_keys`` columns, in blocks of ``block``,
    compared against every candidate) and keeps the largest digit that
    ``top`` scores still reach. A tie AT the threshold that the cut
    splits is resolved by a prefix count, in a branch taken only when
    some row has one.
    """
    s, nq, _width = scores.shape
    n_blocks = (n_keys + block - 1) // block
    bits = _ordered_bits(scores)
    floor = _ordered_bits(jnp.float32(-jnp.inf))
    digits = jnp.arange(1, 1 << RADIX_BITS, dtype=jnp.uint32)[:, None, None]

    def count(pred, lead=()):
        """Per row, the columns below ``n_blocks * block`` where
        ``pred(block of bits)`` holds."""
        def body(i, total):
            part = lax.dynamic_slice_in_dim(bits, i * block, block, axis=2)
            return total + jnp.sum(pred(part), axis=-1, dtype=jnp.int32)
        return lax.fori_loop(0, n_blocks, body,
                             jnp.zeros(lead + (s, nq), jnp.int32))

    def settle(b, prefix):
        shift = jnp.uint32(32 - RADIX_BITS) - b.astype(jnp.uint32) \
            * RADIX_BITS
        cands = prefix[None] | (digits << shift)            # (D, S, Q)
        reach = count(lambda part: part[None] >= cands[..., None],
                      lead=cands.shape[:1])
        # fewer scores reach a larger candidate: those that ``top``
        # reach are the first few, and their number is the digit
        digit = jnp.sum(reach >= top, axis=0).astype(jnp.uint32)
        return prefix | (digit << shift)

    # the largest value that at least ``top`` scores reach: the k-th best
    kth = lax.fori_loop(0, 32 // RADIX_BITS, settle,
                        jnp.zeros((s, nq), jnp.uint32))
    kth = jnp.maximum(kth, floor + 1)       # fewer than top keys: all
    above = count(lambda part: part > kth[..., None])
    at = count(lambda part: part == kth[..., None])
    room = top - above                      # of the ties, how many fit
    valid = bits > floor

    def split(_):
        tied = bits == kth[..., None]
        before = jnp.cumsum(tied, axis=-1, dtype=jnp.int32) - tied
        return valid & ((bits > kth[..., None])
                        | (tied & (before < room[..., None])))

    def whole(_):
        return valid & (bits >= kth[..., None])

    return lax.cond(jnp.any(at > room), split, whole, None)


def select_form(nq: int, width: int, block: int) -> str:
    """``"kernel"`` or ``"loop"``: how a chunk of ``nq`` queries chooses
    its keys over a score row of ``width`` columns read in blocks of
    ``block``: ``attention_kernel.py::select_keys_kernel`` on a TPU where
    Mosaic tiles the shapes, :func:`select_keys` elsewhere (shapes and
    backend, as :func:`attention_form`; no knob)."""
    kernel = attention_kernel.select_supported(nq, width, block)
    return "kernel" if kernel and jax.default_backend() == "tpu" else "loop"


def top_positions(scores: jax.Array, top: int) -> tuple[jax.Array, jax.Array]:
    """``lax.top_k`` for a few rows: ``scores`` (S, W) -> ``(positions
    (S, top) int32, which of them are keys (S, top) bool)``."""
    vals, idx = lax.top_k(scores, top)
    return idx.astype(jnp.int32), vals > -jnp.inf


def gathered_attention(q: jax.Array, positions: jax.Array, live: jax.Array,
                       pool_k: jax.Array, pool_v: jax.Array,
                       table: jax.Array, *, page: int) -> jax.Array:
    """Attention of one query a sequence over the keys at ``positions``
    alone: ``q`` (S, nkv, g, hd) bfloat16, scaled; ``positions`` (S, K),
    ``live`` (S, K) bool; ``table`` (S, W) from position 0 on. Returns
    (S, nkv, g, hd) float32 (zeros where nothing is live)."""
    phys = jnp.where(live, jnp.take_along_axis(
        table, jnp.minimum(positions // page, table.shape[1] - 1), axis=1), 0)
    k = pool_k[phys, positions % page]                  # (S, K, nkv, hd)
    v = pool_v[phys, positions % page]
    sc = jnp.einsum("sngd,sknd->sngk", q, k, preferred_element_type=F32)
    sc = jnp.where(live[:, None, None, :], sc, MASKED)
    p = jnp.where(live[:, None, None, :],
                  jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True)), 0.0)
    acc = jnp.einsum("sngk,sknd->sngd", p.astype(BF16), v,
                     preferred_element_type=F32)
    return acc / jnp.maximum(jnp.sum(p, axis=-1), 1e-30)[..., None]


# --------------------------------------------------------------------------
# the step program
# --------------------------------------------------------------------------

LANES = 128


def kv_tail(cfg: LmConfig) -> tuple[int, int]:
    """How a position's K (or V) lies in a page: ``(kv heads, head_dim)``
    or, a head wider than a lane block, ``(kv heads x blocks, 128)``: a
    head of 256 as its two halves, so that a page is rows of 128 lanes
    as the kernels read it without a copy (``attention_kernel.py``); the
    same bytes in the same order as ``(kv heads, 256)``."""
    nkv, hd = cfg.num_key_value_heads, cfg.head_dim
    if hd > LANES and hd % LANES == 0:
        return nkv * hd // LANES, LANES
    return nkv, hd


def empty_cache(cfg: LmConfig, geo: Geometry) -> dict:
    """Per layer one K and one V pool ``(pages, page, *kv_tail)`` and,
    where the model has an indexer, one pool of its keys ``(pages, page,
    index_head_dim)``; the layers of a class share page numbers
    (``cache.py``), and a layer's three pools share them too. A model
    with latent attention has ONE pool a layer in their place, ``lat``
    ``(pages, latent_width, page)`` (positions in the lanes: 576 does
    not fill them and 256 does, so this is how the chip would lay the
    array out anyway), on the full class's page numbers. A model with
    DeltaNet layers has pools for its full layers alone and, for each
    DeltaNet layer, ``state`` ``(rows, value heads, dk, dv)`` float32 and
    ``conv`` ``(rows, taps - 1, conv channels)`` bfloat16: a slot a row."""
    sizes = [geo.window_pages if k == SLIDING else geo.full_pages
             for k in cfg.layer_types if k != LINEAR]

    def pools(*tail):
        return [jnp.zeros((n, geo.page) + tail, BF16) for n in sizes]

    if cfg.latent_width:
        return {"lat": [jnp.zeros((n, cfg.latent_width, geo.page), BF16)
                        for n in sizes]}
    out = {"k": pools(*kv_tail(cfg)), "v": pools(*kv_tail(cfg))}
    if cfg.index_topk:
        out["ki"] = pools(cfg.index_head_dim)
    if cfg.linear_layers:
        # a slot a row (cache.py): the float32 state and the conv tail
        out["state"] = [jnp.zeros(
            (geo.rows, cfg.linear_value_heads, cfg.linear_key_dim,
             cfg.linear_value_dim), F32) for _ in range(cfg.linear_layers)]
        out["conv"] = [jnp.zeros((geo.rows, cfg.linear_conv - 1,
                                  cfg.conv_dim), BF16)
                       for _ in range(cfg.linear_layers)]
    return out


def unpack_ints(cfg: LmConfig, geo: Geometry, ints) -> dict:
    """A step's ``out["ints"]`` (on the host) by name. The last two are
    ``pages`` (a model with window layers) or ``keys`` (one with an
    indexer): what one layer read over what a causal-dense one would. A
    model with hyper-connections ends on ONE, ``hc_defect``: the float32
    bits of the largest distance of a row or column sum of any ``H_res``
    of the step from 1. A model with DeltaNet layers ends on
    ``held_choices``: valid token-choice pairs routed to the experts held
    here and all valid pairs, over its expert layers."""
    r = geo.rows + 1
    n_moe = cfg.num_layers - cfg.num_dense_layers
    out = {"tokens": ints[:r],
           "expert_load": ints[r:r + 3 * n_moe].reshape(n_moe, 3)}
    tail = ints[r + 3 * n_moe:]
    if cfg.hc_mult:
        out["hc_defect"] = float(tail.view("float32")[0])
    elif cfg.linear_layers:
        out["held_choices"] = tail
    else:
        out["keys" if cfg.index_topk else "pages"] = tail
    return out


def plan_shapes(cfg: LmConfig, geo: Geometry, chunk: int) -> dict:
    """``{name: (shape, dtype)}`` of the plan a step of this bucket
    takes (the host stacks it, ``engine.py``). A model without window
    layers has no window table."""
    ring, r = geo.ring(cfg.sliding_window), geo.rows
    out = {"row_active": ((r,), jnp.bool_), "row_pos": ((r,), jnp.int32),
           "row_ftab": ((r, geo.max_pages), jnp.int32)}
    if cfg.window_layers:
        out.update({"row_wtab": ((r, ring), jnp.int32),
                    "row_wbase": ((r,), jnp.int32)})
    if chunk:
        out.update({"chunk_ids": ((chunk,), jnp.int32),
                    # p0, n, row (-1: not the last chunk), window base
                    "chunk_meta": ((4,), jnp.int32),
                    "chunk_ftab": ((geo.max_pages,), jnp.int32)})
        if cfg.window_layers:
            out["chunk_wtab"] = ((ring,), jnp.int32)
        if cfg.linear_layers:       # the chunk's request's state slot
            out["chunk_slot"] = ((1,), jnp.int32)
    return out


@dataclass
class _Step:
    """What every layer of one step reads: the tokens' positions and the
    plan. Chunk tokens come first, then the rows'."""

    chunk: int
    plan: dict
    pos: jax.Array
    valid: jax.Array
    row_pos: jax.Array
    row_on: jax.Array
    row_last: jax.Array
    p0: jax.Array | None = None
    n: jax.Array | None = None
    cwbase: jax.Array | None = None
    offs: jax.Array | None = None

    @property
    def chunk_last(self) -> jax.Array:
        return jnp.where(self.n > 0, self.p0 + self.n - 1, -1)


def _write_pages(st: _Step, geo: Geometry, pools: list, new: list,
                 ctab, cbase, row_tab, row_base) -> list:
    """The chunk's and the rows' new entries into each pool of one layer
    (``new`` (T, ...) a pool: K, V, the indexer's key)."""
    page, chunk = geo.page, st.chunk
    new = [x.reshape(x.shape[:1] + pool.shape[2:])
           for pool, x in zip(pools, new)]
    if chunk:
        first = (st.p0 - cbase) // page
        for j in range(chunk // page):
            slot = jnp.minimum(first + j, ctab.shape[0] - 1)
            phys = jnp.where(j * page < st.n, ctab[slot], 0)
            pools = [lax.dynamic_update_slice(
                pool, x[None, j * page:(j + 1) * page],
                (phys,) + (0,) * (pool.ndim - 1))
                for pool, x in zip(pools, new)]
    slot = jnp.clip((st.row_pos - row_base) // page, 0, row_tab.shape[1] - 1)
    phys = jnp.where(st.row_on, jnp.take_along_axis(
        row_tab, slot[:, None], axis=1)[:, 0], 0)
    return [pool.at[phys, st.row_pos % page].set(x[chunk:])
            for pool, x in zip(pools, new)]


def _write_latents(st: _Step, geo: Geometry, pool: jax.Array,
                   new: jax.Array, ctab, row_tab) -> jax.Array:
    """:func:`_write_pages` for a pool with the positions in the lanes,
    ``(pages, width, page)``: ``new`` (T, width)."""
    page, chunk = geo.page, st.chunk
    if chunk:
        first = st.p0 // page
        for j in range(chunk // page):
            slot = jnp.minimum(first + j, ctab.shape[0] - 1)
            phys = jnp.where(j * page < st.n, ctab[slot], 0)
            pool = lax.dynamic_update_slice(
                pool, new[j * page:(j + 1) * page].T[None], (phys, 0, 0))
    slot = jnp.clip(st.row_pos // page, 0, row_tab.shape[1] - 1)
    phys = jnp.where(st.row_on, jnp.take_along_axis(
        row_tab, slot[:, None], axis=1)[:, 0], 0)
    rows = new[chunk:].T[None]                              # (1, width, R)

    def one(r, pool):
        # a column a row, in place: a scatter would ask for the width
        # in the lanes and relay the whole pool out and back (880 MB a
        # layer each way in the compiled step)
        return lax.dynamic_update_slice(
            pool, lax.dynamic_slice_in_dim(rows, r, 1, axis=2),
            (phys[r], 0, st.row_pos[r] % page))

    return lax.fori_loop(0, rows.shape[2], one, pool)


def _experts(cfg: LmConfig, lp: dict, x: jax.Array, valid: jax.Array):
    """Router and routed experts of one layer: ``(y, load (3,))``."""
    chosen, weights, _ = moe.route(
        x, lp["router"], lp.get("bias"), top_k=cfg.num_experts_per_tok,
        route_norm=cfg.route_norm, route_scale=cfg.route_scale,
        score_func=cfg.score_func)
    y, counted, busy = moe.experts(x, chosen, weights, lp["e_gate"],
                                   lp["e_up"], lp["e_down"], valid,
                                   held=cfg.held)
    return y, jnp.stack([jnp.max(counted), jnp.sum(counted), busy])


def _afmoe_layer(cfg: LmConfig, geo: Geometry, st: _Step, li: int, lp: dict,
                 h: jax.Array, kv: dict):
    """One ``afmoe`` layer; returns ``(h, expert load or None, (pages
    visited, pages a causal-full layer would) or None)``."""
    chunk, page, plan = st.chunk, geo.page, st.plan
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    g, eps = nh // nkv, cfg.rms_norm_eps
    is_window = cfg.layer_types[li] == SLIDING
    window = cfg.sliding_window if is_window else None
    x = rms_norm(h, lp["n1"], eps)
    q = rms_norm(mm(x, lp["wq"]).reshape(-1, nh, hd), lp["qn"], eps)
    k = rms_norm(mm(x, lp["wk"]).reshape(-1, nkv, hd), lp["kn"], eps)
    v = mm(x, lp["wv"]).reshape(-1, nkv, hd)
    if is_window:
        q = rope(q, st.pos, cfg.rope_theta)
        k = rope(k, st.pos, cfg.rope_theta)
    q = (q * (1.0 / math.sqrt(hd))).astype(BF16).reshape(-1, nkv, g, hd)
    k, v = k.astype(BF16), v.astype(BF16)
    if is_window:
        row_tab, row_base = plan["row_wtab"], plan["row_wbase"]
    else:
        row_tab, row_base = plan["row_ftab"], jnp.zeros_like(st.row_pos)
    ctab = cbase = None
    if chunk:
        ctab = plan["chunk_wtab"] if is_window else plan["chunk_ftab"]
        cbase = st.cwbase if is_window else jnp.int32(0)
    with jax.named_scope("lm.cache.write"):
        pk, pv = _write_pages(st, geo, [kv["k"][li], kv["v"][li]], [k, v],
                              ctab, cbase, row_tab, row_base)
    kv["k"][li], kv["v"][li] = pk, pv
    pages = None
    with jax.named_scope("lm.attn.window" if is_window else "lm.attn.full"):
        o_rows, seen = paged_attention(
            q[chunk:, None], st.row_pos[:, None], st.row_last, pk, pv,
            row_tab, row_base, window=window, page=page,
            block_pages=geo.kv_block_pages)
        o = o_rows[:, 0]
        would = jnp.where(st.row_on, st.row_pos // page + 1, 0)
        if chunk:
            o_chunk, c_seen = paged_attention(
                q[None, :chunk], (st.p0 + st.offs)[None],
                st.chunk_last[None], pk, pv, ctab[None], cbase[None],
                window=window, page=page, block_pages=geo.kv_block_pages)
            o = jnp.concatenate([o_chunk[0], o])
            seen = jnp.concatenate([seen, c_seen])
            would = jnp.concatenate([would, jnp.where(
                st.n > 0, (st.p0 + st.n - 1) // page + 1, 0)[None]])
        if is_window and li == cfg.layer_types.index(SLIDING):
            pages = jnp.stack([jnp.sum(seen), jnp.sum(would)])
    with jax.named_scope("lm.attn.gate"):
        o = o.reshape(-1, nh * hd) * jax.nn.sigmoid(mm(x, lp["wg"]))
    h = h + rms_norm(mm(o, lp["wo"]), lp["n2"], eps)

    x = rms_norm(h, lp["n3"], eps)
    load = None
    if li < cfg.num_dense_layers:
        with jax.named_scope("lm.mlp.dense"):
            y = moe.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
    else:
        y, load = _experts(cfg, lp, x, st.valid)
        if cfg.num_shared_experts:
            with jax.named_scope("lm.moe.shared"):
                y = y + moe.swiglu(x, lp["s_gate"], lp["s_up"],
                                   lp["s_down"])
    return h + rms_norm(y, lp["n4"], eps), load, pages


def _sparse_layer(cfg: LmConfig, geo: Geometry, st: _Step, li: int, lp: dict,
                  h: jax.Array, kv: dict):
    """One ``KeyeVL2`` layer; returns ``(h, expert load, (keys attended,
    keys a causal-dense layer would have) of the first layer)``."""
    chunk, page, plan = st.chunk, geo.page, st.plan
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    g, eps, theta = nh // nkv, cfg.rms_norm_eps, cfg.rope_theta
    ih, idim, top = cfg.index_heads, cfg.index_head_dim, cfg.index_topk
    blocks = dict(page=page, block_pages=geo.kv_block_pages)
    x = rms_norm(h, lp["n1"], eps)
    q = rms_norm(mm(x, lp["wq"]).reshape(-1, nh, hd), lp["qn"], eps)
    k = rms_norm(mm(x, lp["wk"]).reshape(-1, nkv, hd), lp["kn"], eps)
    v = mm(x, lp["wv"]).reshape(-1, nkv, hd)
    q = (rope(q, st.pos, theta) * (1.0 / math.sqrt(hd))).astype(
        BF16).reshape(-1, nkv, g, hd)
    k, v = rope(k, st.pos, theta).astype(BF16), v.astype(BF16)
    with jax.named_scope("lm.attn.index"):
        qi = rope(mm(x, lp["iq"]).reshape(-1, ih, idim), st.pos,
                  theta).astype(BF16)
        ki = rope(layer_norm(mm(x, lp["ik"]), lp["ikn"], lp["ikb"],
                             eps)[:, None, :], st.pos, theta)[:, 0].astype(
                                 BF16)
        wi = mm(x, lp["iw"]) * (ih ** -0.5 * idim ** -0.5)
    row_tab, zero = plan["row_ftab"], jnp.zeros_like(st.row_pos)
    ctab = plan["chunk_ftab"] if chunk else None
    with jax.named_scope("lm.cache.write"):
        pk, pv, pki = _write_pages(
            st, geo, [kv["k"][li], kv["v"][li], kv["ki"][li]], [k, v, ki],
            ctab, jnp.int32(0), row_tab, zero)
    kv["k"][li], kv["v"][li], kv["ki"][li] = pk, pv, pki

    # the rows: one query each; lax.top_k, then the chosen keys alone
    with jax.named_scope("lm.attn.index"):
        scores = index_scores(qi[chunk:, None], wi[chunk:, None],
                              st.row_pos[:, None], st.row_last, pki, row_tab,
                              width=geo.key_width, **blocks)
    with jax.named_scope("lm.attn.select"):
        positions, live = top_positions(scores[:, 0], top)
    with jax.named_scope("lm.attn.sparse"):
        o = gathered_attention(q[chunk:], positions, live, pk, pv, row_tab,
                               page=page)
    if chunk:
        # the chunk: every query its own set, as a mask over the pages
        # that all of them read
        with jax.named_scope("lm.attn.index"):
            scores = index_scores(qi[None, :chunk], wi[None, :chunk],
                                  (st.p0 + st.offs)[None],
                                  st.chunk_last[None], pki, ctab[None],
                                  width=geo.key_width, **blocks)
        block = geo.kv_block_pages * page
        with jax.named_scope("lm.attn.select"):
            if select_form(chunk, geo.key_width, block) == "kernel":
                chosen = attention_kernel.select_keys_kernel(
                    scores[0], top, st.p0, st.p0 + st.n, block=block)[None]
            else:
                chosen = select_keys(scores, top, st.p0 + st.n, block=block)
        with jax.named_scope("lm.attn.sparse"):
            o_chunk, _ = paged_attention(
                q[None, :chunk], (st.p0 + st.offs)[None],
                st.chunk_last[None], pk, pv, ctab[None],
                jnp.zeros((1,), jnp.int32), window=None, chosen=chosen,
                **blocks)
        o = jnp.concatenate([o_chunk[0], o])
    keys = None
    if li == 0:
        causal = jnp.where(st.valid, st.pos + 1, 0)
        keys = jnp.stack([jnp.sum(jnp.minimum(causal, top)),
                          jnp.sum(causal)])
    h = h + mm(o.reshape(-1, nh * hd), lp["wo"])
    y, load = _experts(cfg, lp, rms_norm(h, lp["n2"], eps), st.valid)
    return h + y, load, keys


def latent_rows_form(nh: int, latent: int, page: int) -> str:
    """``"kernel"`` or ``"loop"``: how :func:`absorbed_attention` reads
    the rows' pages (shapes and backend, as :func:`attention_form`)."""
    kernel = attention_kernel.latent_rows_supported(nh, latent, page)
    return "kernel" if kernel and jax.default_backend() == "tpu" else "loop"


def absorbed_attention(q: jax.Array, qpos: jax.Array, last_pos: jax.Array,
                       pool: jax.Array, table: jax.Array, w_kvb: jax.Array,
                       *, nope: int, scale: float, page: int,
                       block_pages: int) -> jax.Array:
    """Latent attention of ONE query a sequence in the absorbed form:
    ``q`` (S, heads, nope + rope) float32 (rope part rotated), ``qpos``
    (S,), ``last_pos`` (S,), ``pool`` (pages, rank + rope, page) bfloat16,
    ``table`` (S, W) from position 0 on, ``w_kvb`` (rank, heads, nope +
    v). ``q_lat = q_nope Wuk^T``, scores against the latent itself, whose
    first ``rank`` lanes are also the value (read once, for every head
    and both products), ``o = (softmax(s) c) Wuv``. On a TPU the pages
    are read by one kernel whose work is the sum of the rows' contexts
    (``attention_kernel.py::latent_rows_attention``); elsewhere by the
    loop of :func:`paged_attention`, every row as long as the longest.
    Returns (S, heads, v) float32."""
    rank = w_kvb.shape[0]
    q_lat = jnp.einsum("thd,chd->thc", q[..., :nope].astype(BF16),
                       w_kvb[..., :nope], preferred_element_type=F32)
    qa = (jnp.concatenate([q_lat, q[..., nope:]], axis=-1)
          * scale).astype(BF16)
    if latent_rows_form(q.shape[1], pool.shape[1], page) == "kernel":
        o_lat = attention_kernel.latent_rows_attention(
            qa, last_pos, pool, table, page=page, block_pages=block_pages)
    else:
        # the value IS the key's array: the product runs over all of the
        # latent's lanes and the rope's 64 are dropped after, which costs
        # an eighth more operations of a pass that waits on memory and
        # saves a copy of every block's first 512
        o_lat, _ = paged_attention(
            qa[:, None, None], qpos[:, None], last_pos, pool, None, table,
            jnp.zeros_like(qpos), window=None, page=page,
            block_pages=block_pages, value_width=pool.shape[1],
            keys_minor=True, expand=lambda blk: (blk, blk))
        o_lat = o_lat[:, 0, 0]
    return jnp.einsum("thc,chd->thd", o_lat[..., :rank].astype(BF16),
                      w_kvb[..., nope:], preferred_element_type=F32)


def latent_chunk_form(nq: int, nope: int, rope_dim: int, rank: int, vd: int,
                      page: int) -> str:
    """``"latent_expanded_kernel"`` or ``"latent_expanded_loop"``: the
    form :func:`expanded_attention` takes for a chunk of ``nq`` queries
    (shapes and backend, as :func:`attention_form`; no knob)."""
    kernel = attention_kernel.latent_supported(nq, nope, rope_dim, rank, vd,
                                               page)
    return "latent_expanded_kernel" \
        if kernel and jax.default_backend() == "tpu" \
        else "latent_expanded_loop"


def expanded_attention(q: jax.Array, p0: jax.Array, last_pos: jax.Array,
                       pool: jax.Array, table: jax.Array, w_kvb: jax.Array,
                       *, nope: int, scale: float, page: int,
                       block_pages: int) -> jax.Array:
    """Latent attention of a prefill chunk in the expanded form: ``q``
    (Q, heads, nope + rope) float32 at positions ``p0 + arange(Q)`` of
    one sequence, ``pool`` (pages, rank + rope, page), ``table`` (W,). A
    block of the context's latents goes through ``Wkvb`` ONCE for all of
    the chunk's queries and is attended as ``heads`` K/V heads of ``nope
    + rope`` / ``v``, the one rope key copied to each. Two forms (:func:`latent_chunk_form`): on a TPU one
    kernel that expands a block for a head in VMEM
    (``attention_kernel.py::latent_chunk_attention``); elsewhere the
    loop below (scope ``lm.attn.latent.expand`` for its expansion).
    Returns (Q, heads, v) float32."""
    rank, nh, wide = w_kvb.shape
    if latent_chunk_form(q.shape[0], nope, q.shape[-1] - nope, rank,
                         wide - nope, page) == "latent_expanded_kernel":
        return attention_kernel.latent_chunk_attention(
            (q * scale).astype(BF16), p0,
            jnp.where(last_pos >= 0, last_pos // page + 1, 0), pool, table,
            w_kvb, nope=nope, page=page, block_pages=block_pages)

    def expand(blk):
        with jax.named_scope("lm.attn.latent.expand"):
            lat = blk[0].transpose(0, 2, 1).reshape(
                -1, blk.shape[2])                           # (keys, 576)
            full = jnp.einsum("kc,chd->khd", lat[:, :rank], w_kvb,
                              preferred_element_type=F32).astype(BF16)
            k = jnp.concatenate([full[..., :nope], jnp.broadcast_to(
                lat[:, None, rank:], (lat.shape[0], nh, lat.shape[1] - rank)
            )], axis=-1)
            return k[None], full[None, ..., nope:]

    out, _ = paged_attention(
        (q * scale).astype(BF16)[None, :, :, None],
        (p0 + jnp.arange(q.shape[0], dtype=jnp.int32))[None], last_pos[None],
        pool, None, table[None], jnp.zeros((1,), jnp.int32),
        window=None, page=page, block_pages=block_pages,
        value_width=wide - nope, expand=expand)
    return out[0, :, :, 0]


def _latent_layer(cfg: LmConfig, geo: Geometry, st: _Step, li: int, lp: dict,
                  xs: jax.Array, kv: dict):
    """One ``xing4_0`` layer on the residual state ``xs`` (T, streams,
    H); returns ``(xs, expert load or None, the largest defect of its
    two ``H_res`` over the valid tokens (1,))``."""
    chunk, plan = st.chunk, st.plan
    nh, eps = cfg.num_attention_heads, cfg.rms_norm_eps
    rank, nope, vd = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    how = dict(nope=nope, scale=latent_scale(cfg), page=geo.page,
               block_pages=geo.kv_block_pages)
    w_kvb = lp["wkvb"].reshape(rank, nh, nope + vd)

    def attention(u):
        x = rms_norm(u, lp["n1"], eps)
        with jax.named_scope("lm.attn.latent.project"):
            q = mm(rms_norm(mm(x, lp["wqa"]), lp["qan"], eps),
                   lp["wqb"]).reshape(-1, nh, cfg.head_dim)
            q = jnp.concatenate(
                [q[..., :nope], latent_rope(cfg, q[..., nope:], st.pos)],
                axis=-1)
            kva = mm(x, lp["wkva"])
            latent = jnp.concatenate(
                [rms_norm(kva[:, :rank], lp["kvn"], eps),
                 latent_rope(cfg, kva[:, None, rank:], st.pos)[:, 0]],
                axis=-1).astype(BF16)
        row_tab = plan["row_ftab"]
        ctab = plan["chunk_ftab"] if chunk else None
        with jax.named_scope("lm.cache.write"):
            pool = _write_latents(st, geo, kv["lat"][li], latent, ctab,
                                  row_tab)
        kv["lat"][li] = pool
        with jax.named_scope("lm.attn.latent.rows"):
            o = absorbed_attention(q[chunk:], st.row_pos, st.row_last, pool,
                                   row_tab, w_kvb, **how)
        if chunk:
            with jax.named_scope("lm.attn.latent.chunk"):
                o = jnp.concatenate([expanded_attention(
                    q[:chunk], st.p0, st.chunk_last, pool, ctab, w_kvb,
                    **how), o])
        with jax.named_scope("lm.attn.latent.project"):
            return mm(o.reshape(-1, nh * vd), lp["wo"])

    load = []

    def mlp(u):
        x = rms_norm(u, lp["n2"], eps)
        if li < cfg.num_dense_layers:
            with jax.named_scope("lm.mlp.dense"):
                return moe.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
        y, counted = _experts(cfg, lp, x, st.valid)
        load.append(counted)
        if cfg.num_shared_experts:
            with jax.named_scope("lm.moe.shared"):
                y = y + moe.swiglu(x, lp["s_gate"], lp["s_up"], lp["s_down"])
        return y

    xs, d_attn = hyper_connect(
        cfg, xs, (lp["hca_w"], lp["hca_b"], lp["hca_a"]), attention)
    xs, d_mlp = hyper_connect(
        cfg, xs, (lp["hcm_w"], lp["hcm_b"], lp["hcm_a"]), mlp)
    defect = jnp.max(jnp.where(st.valid, jnp.maximum(d_attn, d_mlp), 0.0))
    return xs, load[0] if load else None, defect[None]


# --------------------------------------------------------------------------
# Gated DeltaNet and gated attention (qwen3_next)
# --------------------------------------------------------------------------

def _hi(spec: str, *xs) -> jax.Array:
    return jnp.einsum(spec, *xs, precision=lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gdn_chunk(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
              beta: jax.Array, state: jax.Array, *, sub: int
              ) -> tuple[jax.Array, jax.Array]:
    """The gated delta rule over ``T`` consecutive positions of one
    sequence, chunkwise: ``q`` ``k`` (T, H, dk) (q scaled), ``v`` (T, H,
    dv), ``g`` ``beta`` (T, H), ``state`` (H, dk, dv), all float32; a
    position with ``beta`` and ``g`` 0 leaves the state as it was.
    Returns ``(o (T, H, dv), the state after the last position)``.

    Within a sub-chunk of ``sub`` positions the recurrence is the WY
    form: with ``G`` the cumulative gates and ``D[i, j] = e^(G_i - G_j)``
    (i >= j), ``A = strict_lower((beta k) k^T * D)``, ``T = (I + A)^-1``
    (the product ``(I - A)(I + A^2)(I + A^4)...``: A is nilpotent),
    ``U = T (beta v)``, ``W = T (beta k e^G)``; then, the state carried
    from one sub-chunk to the next, ``V = U - W S``, ``o = (q e^G) S +
    lower(q k^T * D) V`` and ``S <- e^(G_last) S + (k e^(G_last - G))^T
    V``. Every product at ``HIGHEST``."""
    t, h, dk = k.shape
    n = t // sub

    def split(x):
        return x.reshape((n, sub, h) + x.shape[2:]).swapaxes(1, 2)

    q, k, v, g, beta = map(split, (q, k, v, g, beta))   # (n, H, sub, ...)
    gc = jnp.cumsum(g, axis=-1)
    i = lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    j = lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    decay = jnp.where(i >= j, jnp.exp(jnp.where(
        i >= j, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
    kb = k * beta[..., None]
    a = jnp.where(i > j, _hi("nhid,nhjd->nhij", kb, k) * decay, 0.0)
    # (I + A)^-1 = (I - A)(I + A^2)(I + A^4)... while a power is nonzero
    inv = jnp.eye(sub, dtype=F32) - a
    power = a
    for _ in range(max(sub - 1, 1).bit_length() - 1):
        power = _hi("nhij,nhjk->nhik", power, power)
        inv = inv + _hi("nhij,nhjk->nhik", inv, power)
    u = _hi("nhij,nhjd->nhid", inv, v * beta[..., None])
    w = _hi("nhij,nhjd->nhid", inv, kb * jnp.exp(gc)[..., None])
    qk = jnp.where(i >= j, _hi("nhid,nhjd->nhij", q, k) * decay, 0.0)

    def body(s, xs):
        qi, ki, ui, wi, gi, qki = xs
        v_new = ui - _hi("hid,hde->hie", wi, s)
        o = _hi("hid,hde->hie", qi * jnp.exp(gi)[..., None], s) \
            + _hi("hij,hje->hie", qki, v_new)
        last = gi[:, -1]
        s = s * jnp.exp(last)[:, None, None] + _hi(
            "hid,hie->hde", ki * jnp.exp(last[:, None] - gi)[..., None],
            v_new)
        return s, o

    state, o = lax.scan(body, state, (q, k, u, w, gc, qk))
    return o.swapaxes(1, 2).reshape(t, h, -1), state


def gdn_rows(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, state: jax.Array
             ) -> tuple[jax.Array, jax.Array]:
    """One step of the gated delta rule for each of ``R`` sequences:
    ``q`` ``k`` (R, H, dk), ``v`` (R, H, dv), ``g`` ``beta`` (R, H),
    ``state`` (R, H, dk, dv), float32. ``S <- e^g S``, ``S <- S + k ((v -
    S^T k) beta)^T``, ``o = S^T q``: elementwise products and sums, a
    read and a write of every state. Returns ``(o (R, H, dv), state)``."""
    s = state * jnp.exp(g)[..., None, None]
    remembered = jnp.sum(s * k[..., :, None], axis=-2)
    s = s + k[..., :, None] * ((v - remembered) * beta[..., None])[
        ..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


def _conv_tail(p0: jax.Array, tail: jax.Array, x: jax.Array,
               w: jax.Array, n: jax.Array) -> tuple[jax.Array, jax.Array]:
    """A chunk's causal depthwise conv: ``tail`` (K - 1, C) the inputs
    before position ``p0`` (ignored at 0), ``x`` (chunk, C), ``w`` (K,
    C), ``n`` live positions. Returns ``(out (chunk, C), the new tail)``."""
    taps = w.shape[0]
    xp = jnp.concatenate([jnp.where(p0 == 0, 0.0, tail.astype(F32)), x])
    out = sum(w[j].astype(F32) * xp[j:j + x.shape[0]] for j in range(taps))
    return out, lax.dynamic_slice_in_dim(xp, n, taps - 1)


def _gdn_mixer(cfg: LmConfig, st: _Step, di: int, lp: dict, x: jax.Array,
               kv: dict) -> jax.Array:
    """One Gated DeltaNet mixer (the module docstring) over a step's
    tokens: the chunk's through :func:`gdn_chunk` from its slot's state
    (zeros where the chunk starts at position 0), the rows' through
    :func:`gdn_rows` from theirs (a row's slot is its row); both written
    back in place, the rows' where a row is live. ``di``: the layer's
    place among the DeltaNet layers."""
    chunk = st.chunk
    nk, nv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv, r = cfg.linear_key_dim, cfg.linear_value_dim, nv // nk
    t = x.shape[0]
    with jax.named_scope("lm.gdn.project"):
        qkvz = mm(x, lp["w_qkvz"]).reshape(t, nk, 2 * dk + 2 * r * dv)
        q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
        v = qkvz[..., 2 * dk:2 * dk + r * dv]
        z = qkvz[..., 2 * dk + r * dv:].reshape(t, nv, dv)
        ba = mm(x, lp["w_ba"]).reshape(t, nk, 2 * r)
        b, a = ba[..., :r].reshape(t, nv), ba[..., r:].reshape(t, nv)
        mixed = jnp.concatenate([q.reshape(t, -1), k.reshape(t, -1),
                                 v.reshape(t, -1)], axis=-1)
    state, tails = kv["state"][di], kv["conv"][di]
    w = lp["conv"]
    with jax.named_scope("lm.gdn.conv"):
        xp = jnp.concatenate([tails.astype(F32), mixed[chunk:, None]], 1)
        y_rows = sum(w[j].astype(F32) * xp[:, j] for j in range(w.shape[0]))
        tails = jnp.where(st.row_on[:, None, None], xp[:, 1:].astype(BF16),
                          tails)
        ys = [y_rows]
        if chunk:
            slot = st.plan["chunk_slot"][0]
            y_chunk, tail = _conv_tail(st.p0, tails[slot], mixed[:chunk],
                                       w, st.n)
            tails = lax.dynamic_update_index_in_dim(
                tails, tail.astype(BF16), slot, 0)
            ys = [y_chunk] + ys
        y = jax.nn.silu(jnp.concatenate(ys))
        kd = nk * dk
        q = jnp.repeat(l2norm(y[:, :kd].reshape(t, nk, dk)) * dk ** -0.5, r,
                       axis=1)
        k = jnp.repeat(l2norm(y[:, kd:2 * kd].reshape(t, nk, dk)), r, axis=1)
        v = y[:, 2 * kd:].reshape(t, nv, dv)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(lp["a_log"].astype(F32)) * jax.nn.softplus(
            a + lp["dt_bias"].astype(F32))
    with jax.named_scope("lm.gdn.rows"):
        o_rows, s_rows = gdn_rows(q[chunk:], k[chunk:], v[chunk:],
                                  g[chunk:], beta[chunk:], state)
        state = jnp.where(st.row_on[:, None, None, None], s_rows, state)
        outs = [o_rows]
    if chunk:
        with jax.named_scope("lm.gdn.chunk"):
            live = (st.offs < st.n)[:, None]
            s0 = jnp.where(st.p0 == 0, 0.0, state[slot])
            o_chunk, s_chunk = gdn_chunk(
                q[:chunk], k[:chunk], v[:chunk],
                jnp.where(live, g[:chunk], 0.0),
                jnp.where(live, beta[:chunk], 0.0), s0,
                sub=min(GDN_SUB, chunk))
            state = lax.dynamic_update_index_in_dim(state, s_chunk, slot, 0)
            outs = [o_chunk] + outs
    kv["state"][di], kv["conv"][di] = state, tails
    with jax.named_scope("lm.gdn.out"):
        o = jnp.concatenate(outs)
        o = rms_norm(o, lp["norm"], cfg.rms_norm_eps) * jax.nn.silu(z)
        return mm(o.reshape(t, nv * dv), lp["w_out"])


def partial_rope(x: jax.Array, pos: jax.Array, theta: float, dims: int
                 ) -> jax.Array:
    """Rotate-half rotary over the first ``dims`` of each head's dims."""
    return jnp.concatenate([rope(x[..., :dims], pos, theta),
                            x[..., dims:]], axis=-1)


def _gated_attention(cfg: LmConfig, geo: Geometry, st: _Step, fi: int,
                     lp: dict, x: jax.Array, kv: dict) -> jax.Array:
    """One gated full attention mixer over K/V pages (the module
    docstring); ``fi``: the layer's place among the full layers, whose
    pools alone exist."""
    chunk, page, plan = st.chunk, geo.page, st.plan
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    g, eps = nh // nkv, cfg.rms_norm_eps
    qg = mm(x, lp["wq"]).reshape(-1, nh, 2 * hd)
    q = rms_norm(qg[..., :hd], 1.0 + lp["qn"].astype(F32), eps)
    k = rms_norm(mm(x, lp["wk"]).reshape(-1, nkv, hd),
                 1.0 + lp["kn"].astype(F32), eps)
    v = mm(x, lp["wv"]).reshape(-1, nkv, hd)
    q = partial_rope(q, st.pos, cfg.rope_theta, cfg.rotary_dim)
    k = partial_rope(k, st.pos, cfg.rope_theta, cfg.rotary_dim)
    q = (q * hd ** -0.5).astype(BF16).reshape(-1, nkv, g, hd)
    k, v = k.astype(BF16), v.astype(BF16)
    row_tab, zero = plan["row_ftab"], jnp.zeros_like(st.row_pos)
    ctab = plan["chunk_ftab"] if chunk else None
    with jax.named_scope("lm.cache.write"):
        pk, pv = _write_pages(st, geo, [kv["k"][fi], kv["v"][fi]], [k, v],
                              ctab, jnp.int32(0), row_tab, zero)
    kv["k"][fi], kv["v"][fi] = pk, pv
    with jax.named_scope("lm.attn.full"):
        o, _ = paged_attention(
            q[chunk:, None], st.row_pos[:, None], st.row_last, pk, pv,
            row_tab, zero, window=None, page=page,
            block_pages=geo.kv_block_pages)
        o = o[:, 0]
        if chunk:
            o_chunk, _ = paged_attention(
                q[None, :chunk], (st.p0 + st.offs)[None],
                st.chunk_last[None], pk, pv, ctab[None],
                jnp.zeros((1,), jnp.int32), window=None, page=page,
                block_pages=geo.kv_block_pages)
            o = jnp.concatenate([o_chunk[0], o])
        o = o.reshape(-1, nh * hd) * jax.nn.sigmoid(
            qg[..., hd:].reshape(-1, nh * hd))
    return mm(o, lp["wo"])


def _hybrid_layer(cfg: LmConfig, geo: Geometry, st: _Step, li: int,
                  lp: dict, h: jax.Array, kv: dict):
    """One ``qwen3_next`` layer; returns ``(h, expert load, (valid
    token-choice pairs on the experts held here, all valid pairs))``."""
    eps = cfg.rms_norm_eps
    kind = cfg.layer_types[li]
    x = rms_norm(h, 1.0 + lp["n1"].astype(F32), eps)
    if kind == LINEAR:
        h = h + _gdn_mixer(cfg, st, cfg.layer_types[:li].count(LINEAR), lp,
                           x, kv)
    else:
        h = h + _gated_attention(cfg, geo, st,
                                 cfg.layer_types[:li].count(FULL), lp, x, kv)
    x = rms_norm(h, 1.0 + lp["n2"].astype(F32), eps)
    y, load = _experts(cfg, lp, x, st.valid)
    with jax.named_scope("lm.moe.shared"):
        y = y + jax.nn.sigmoid(mm(x, lp["sg"])) * moe.swiglu(
            x, lp["s_gate"], lp["s_up"], lp["s_down"])
    pairs = jnp.stack([load[1], jnp.sum(st.valid.astype(jnp.int32))
                       * cfg.num_experts_per_tok])
    return h + y, load, pairs


def build_step(cfg: LmConfig, geo: Geometry, chunk: int):
    """``step(params, kv, last_tok, plan) -> (kv, last_tok, out)`` for
    one bucket: ``chunk`` prefill tokens (0: none) beside ``geo.rows``
    decoding rows. ``out``: ``logits`` (rows + 1, V) float32, the rows'
    and, last, the chunk's last position's; ``ints`` one int32 vector
    (:func:`unpack_ints`): ``tokens`` (rows + 1,) their argmax,
    ``expert_load`` (expert layers, 3) the fullest expert's and all
    experts' valid tokens and the experts that hold any row, then
    ``pages`` (2,) pages one window layer visited and pages a
    causal-full layer would have or, for a model with an indexer,
    ``keys`` (2,) keys one layer attended and keys a causal-dense layer
    would have or, for a model with hyper-connections, ``hc_defect``
    (1,) or, for a model with DeltaNet layers, ``held_choices`` (2,)
    (:func:`unpack_ints`). Between the embedding and the head such
    a model's activation is its residual state ``(tokens, hc_mult,
    hidden)``: the embedding copied into every stream, the streams
    summed before the final norm."""
    geo.check(cfg)
    r = geo.rows
    eps = cfg.rms_norm_eps
    layer = _latent_layer if cfg.latent_width else \
        _sparse_layer if cfg.index_topk else \
        _hybrid_layer if cfg.linear_layers else _afmoe_layer

    def step(params, kv, last_tok, plan):
        row_pos = plan["row_pos"]
        row_on = plan["row_active"]
        st = _Step(chunk, plan, row_pos, row_on, row_pos, row_on,
                   jnp.where(row_on, row_pos, -1))
        if chunk:
            st.p0, st.n, chunk_row, st.cwbase = (plan["chunk_meta"][i]
                                                 for i in range(4))
            st.offs = jnp.arange(chunk, dtype=jnp.int32)
            ids = jnp.concatenate([plan["chunk_ids"], last_tok])
            st.pos = jnp.concatenate([st.p0 + st.offs, row_pos])
            st.valid = jnp.concatenate([st.offs < st.n, row_on])
        else:
            ids = last_tok
        with jax.named_scope("lm.embed"):
            h = params["embed"][ids].astype(F32)
            if cfg.mup_enabled:
                h = h * math.sqrt(cfg.hidden_size)
            if cfg.hc_mult:
                h = jnp.repeat(h[:, None, :], cfg.hc_mult, axis=1)

        new = {name: list(pools) for name, pools in kv.items()}
        loads = []
        read = jnp.zeros((1,), F32) if cfg.hc_mult \
            else jnp.zeros((2,), jnp.int32)
        for li, lp in enumerate(params["layers"]):
            h, load, counted = layer(cfg, geo, st, li, lp, h, new)
            if load is not None:
                loads.append(load)
            if counted is not None:
                # the worst H_res of every layer; the held choices of
                # every layer; else one layer's count
                read = jnp.maximum(read, counted) if cfg.hc_mult \
                    else read + counted if cfg.linear_layers else counted
        if cfg.hc_mult:
            read = lax.bitcast_convert_type(read, jnp.int32)

        with jax.named_scope("lm.head"):
            if chunk:
                top = jnp.concatenate([h[chunk:],
                                       h[jnp.maximum(st.n - 1, 0)][None]])
            else:
                top = jnp.concatenate([h, jnp.zeros_like(h[:1])])
            if cfg.hc_mult:
                top = jnp.sum(top, axis=1)
            norm = params["final_norm"].astype(F32)
            if cfg.linear_layers:           # zero-centred, as every norm
                norm = 1.0 + norm
            logits = mm(rms_norm(top, norm, eps), params["head"])
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(row_on, tokens[:r], last_tok)
        if chunk:
            nxt = nxt.at[jnp.where(chunk_row >= 0, chunk_row, r)].set(
                tokens[r], mode="drop")
        out = {"logits": logits, "ints": jnp.concatenate(
            [tokens] + [x.astype(jnp.int32) for x in loads] + [read])}
        return new, nxt, out

    step.__name__ = f"lm_step_c{chunk}"
    step.__qualname__ = step.__name__
    # the forms the rows' and the chunk's attention and the chunk's choice
    # of keys take in this program (the chunk's None where the bucket has
    # none, the choice's also where the model has no indexer)
    step.attn_select_form = select_form(
        chunk, geo.key_width, geo.kv_block_pages * geo.page) \
        if chunk and cfg.index_topk else None
    # the experts' combine (None where every layer is dense)
    step.moe_combine_form = moe.combine_form(
        chunk + r, cfg.num_experts_per_tok, cfg.hidden_size) \
        if cfg.num_layers > cfg.num_dense_layers else None
    # the DeltaNet's two forms: XLA on every backend (no kernel yet)
    step.gdn_rows_form = "recurrent" if cfg.linear_layers else None
    step.gdn_chunk_form = "chunkwise" if cfg.linear_layers and chunk \
        else None
    if cfg.latent_width:
        step.attn_rows_form = "latent_absorbed"
        step.attn_chunk_form = latent_chunk_form(
            chunk, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.kv_lora_rank, cfg.v_head_dim, geo.page) if chunk else None
    else:
        shape = (cfg.num_key_value_heads,
                 cfg.num_attention_heads // cfg.num_key_value_heads,
                 cfg.head_dim, geo.page)
        step.attn_rows_form = "gathered" if cfg.index_topk \
            else attention_form(r, 1, *shape)
        step.attn_chunk_form = attention_form(
            1, chunk, *shape, chosen=bool(cfg.index_topk)) if chunk else None
    return step
