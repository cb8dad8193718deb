"""Operations and bytes of a DeepSeek-V2 step (the deepseek-v2-lite
file), counted as the program does the work: latent attention absorbed
(``q_nope·W_uk``, scores against the cached ``[c ‖ k_pe]`` rows, the
value sum over ``c``, ``·W_uv``, ``W_o``), a dense SwiGLU in the
leading layers, and in each expert layer the router, the shared
experts and the rows routed to the experts held here.

Weights are read once a step.  Of the routed experts, a step reads the
ones its tokens hit: under uniform routing of ``n`` tokens over the
router's ``E`` outputs, each of the ``held`` experts here is hit with
probability ``1 - (1 - k/E)**n``, so the count is that expectation,
and ``n·k·held/E`` rows are routed here (the FLOPs).  Bytes also hold
every live latent row attended (``kv_lora_rank + qk_rope_head_dim``
values a layer, 1,152 B in bf16) and the new rows written.  Idle slots
and padding are not work, as in ``bench/cost.py``.

``experts_decode`` and ``attend_decode`` count the expert layer's and
the latent attention's parts of a decode step alone, for the readers of
those layers.  (No ``from __future__ import annotations``: the harness
loads this file by path, outside ``sys.modules``, where a dataclass
cannot resolve string annotations.)
"""

from dataclasses import dataclass

from bench.cost import DTYPE_BYTES


@dataclass(frozen=True)
class Model:
    L: int            # layers
    lead: int         # leading dense layers
    d: int
    V: int
    H: int
    nope: int         # qk_nope_head_dim
    rope: int         # qk_rope_head_dim
    vd: int           # v_head_dim
    r: int            # kv_lora_rank
    f: int            # dense width
    fe: int           # expert width
    E: int            # the router's outputs
    held: int         # routed experts held here
    k: int            # experts per token
    shared: int       # shared experts
    w_bytes: int
    kv_bytes: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Model":
        held = cfg["n_routed_experts"]
        return cls(L=cfg["num_hidden_layers"],
                   lead=cfg["first_k_dense_replace"], d=cfg["hidden_size"],
                   V=cfg["vocab_size"], H=cfg["num_attention_heads"],
                   nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                   vd=cfg["v_head_dim"], r=cfg["kv_lora_rank"],
                   f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
                   E=cfg.get("published", {}).get("n_routed_experts", held),
                   held=held, k=cfg["num_experts_per_tok"],
                   shared=cfg["n_shared_experts"],
                   w_bytes=DTYPE_BYTES[cfg["dtype"]],
                   kv_bytes=DTYPE_BYTES[cfg["kv_dtype"]])

    @property
    def n_moe(self) -> int:
        return self.L - self.lead

    @property
    def attn_weights(self) -> int:
        """W_q, W_kv_a, W_uk, W_uv and W_o of one layer."""

        d, H, r = self.d, self.H, self.r
        return (d * H * (self.nope + self.rope) + d * (r + self.rope)
                + r * H * (self.nope + self.vd) + H * self.vd * d)

    @property
    def absorb_weights(self) -> int:
        """The weights the latent attention reads: W_uk, W_uv, W_o."""

        return self.r * self.H * (self.nope + self.vd) + self.H * self.vd * self.d

    @property
    def expert_weights(self) -> int:
        return 3 * self.d * self.fe

    @property
    def shared_weights(self) -> int:
        return 3 * self.d * self.shared * self.fe

    @property
    def row_bytes(self) -> int:
        """One token's cached row of one layer."""

        return (self.r + self.rope) * self.kv_bytes

    def hit(self, n: int) -> float:
        """Expected held experts that ``n`` tokens hit, routed uniformly."""

        return self.held * (1.0 - (1.0 - self.k / self.E) ** n) if n else 0.0

    def weight_bytes(self, n: int, head: bool) -> float:
        """Weights a step of ``n`` tokens reads once: every layer's
        attention and norms, the leading layers' MLP, each expert
        layer's float32 router, shared experts and the held experts hit,
        the final norm, and the head where logits are taken."""

        norms = self.r + 2 * self.d
        w = (self.L * (self.attn_weights + norms)
             + self.lead * 3 * self.d * self.f
             + self.n_moe * (self.shared_weights
                             + self.hit(n) * self.expert_weights)
             + self.d + (self.d * self.V if head else 0))
        return w * self.w_bytes + self.n_moe * self.d * self.E * 4

    def attend_flops(self, keys: int) -> float:
        """One query of one layer over ``keys`` latent rows, absorbed:
        q_nope·W_uk, scores, the value sum, ·W_uv, W_o."""

        H, r = self.H, self.r
        return (2 * H * self.nope * r + 2 * H * (r + self.rope) * keys
                + 2 * H * r * keys + 2 * H * r * self.vd
                + 2 * H * self.vd * self.d)

    def token_flops(self, keys: int, head: bool) -> float:
        """One token through every layer, attending ``keys`` keys."""

        d, H, r = self.d, self.H, self.r
        proj = 2 * (d * H * (self.nope + self.rope) + d * (r + self.rope))
        moe = (2 * d * self.E + 2 * self.shared_weights
               + self.k * self.held / self.E * 2 * self.expert_weights)
        fl = (self.L * (proj + self.attend_flops(keys))
              + self.lead * 2 * 3 * d * self.f + self.n_moe * moe)
        return fl + (2 * d * self.V if head else 0)

    def decode(self, keys: list[int]) -> tuple[float, float]:
        """(flops, bytes) of a decode step: one token per listed slot,
        each attending ``keys`` keys (its own new one included)."""

        if not keys:
            return 0.0, 0.0
        flops = sum(self.token_flops(k, True) for k in keys)
        byts = (self.weight_bytes(len(keys), True)
                + self.L * self.row_bytes * (sum(keys) + len(keys)))
        return float(flops), float(byts)

    def prefill(self, chunks: list[tuple[int, int, bool]]
                ) -> tuple[float, float]:
        """(flops, bytes) of a chunked-prefill step: per slot, ``n`` valid
        tokens from position ``start``; logits only where ``emits``."""

        if not chunks:
            return 0.0, 0.0
        flops = 0.0
        keys = tokens = 0
        head = False
        for start, n, emits in chunks:
            # token t attends start + t + 1 keys
            attend = n * start + n * (n + 1) // 2
            flops += (n * (self.token_flops(0, False))
                      + self.L * (2 * self.H * (2 * self.r + self.rope))
                      * attend)
            if emits:
                flops += 2 * self.d * self.V
                head = True
            keys += start + n
            tokens += n
        byts = (self.weight_bytes(tokens, head)
                + self.L * self.row_bytes * (keys + tokens))
        return float(flops), float(byts)

    def experts_decode(self, n: int) -> tuple[float, float]:
        """(flops, bytes) of the held experts' part of a decode step of
        ``n`` tokens: the rows routed here through their SwiGLU, and the
        weights of the experts hit."""

        if not n:
            return 0.0, 0.0
        flops = (self.n_moe * n * self.k * self.held / self.E
                 * 2 * self.expert_weights)
        byts = self.n_moe * self.hit(n) * self.expert_weights * self.w_bytes
        return float(flops), float(byts)

    def attend_decode(self, keys: list[int]) -> tuple[float, float]:
        """(flops, bytes) of the absorbed latent attention of a decode
        step: every layer's live latent rows read once, the new ones
        written, W_uk, W_uv and W_o read once."""

        if not keys:
            return 0.0, 0.0
        flops = self.L * sum(self.attend_flops(k) for k in keys)
        byts = self.L * (self.row_bytes * (sum(keys) + len(keys))
                         + self.absorb_weights * self.w_bytes)
        return float(flops), float(byts)
