"""Operations and bytes of one model step, from the configuration's
sizes and what the step was asked to do.  Only useful work is counted:
the slots that decode or prefill, the valid tokens of a chunk, the
vocabulary head only where logits are taken.  Padding rows, idle slots
and the rest of the pool are not work.

A dense decoder layer holds ``d*H*hd + 2*d*Hkv*hd + H*hd*d`` attention
and ``3*d*f`` MLP weights.  Per token it does 2 operations per weight,
and attention over ``n`` keys costs ``4*H*hd*n`` (scores and the value
sum).  The head is ``d*V`` weights.  Bytes are the weights read once
per step, every live key and value the step attends, and the new rows
it writes.
"""

from __future__ import annotations

from dataclasses import dataclass

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1,
               "float8_e4m3fn": 1}


@dataclass(frozen=True)
class Model:
    L: int
    d: int
    f: int
    H: int
    Hkv: int
    hd: int
    V: int
    bias: bool
    w_bytes: int      # per weight
    kv_bytes: int     # per cached element

    @classmethod
    def from_config(cls, cfg: dict) -> "Model":
        return cls(L=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                   f=cfg["intermediate_size"], H=cfg["num_attention_heads"],
                   Hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                   V=cfg["vocab_size"], bias=bool(cfg["qkv_bias"]),
                   w_bytes=DTYPE_BYTES[cfg["dtype"]],
                   kv_bytes=DTYPE_BYTES[cfg["kv_dtype"]])

    @property
    def layer_weights(self) -> int:
        """Matmul weights of one layer."""

        d, H, Hkv, hd = self.d, self.H, self.Hkv, self.hd
        return d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * self.f

    @property
    def layer_other(self) -> int:
        """Norm weights and biases of one layer."""

        return 2 * self.d + ((self.H + 2 * self.Hkv) * self.hd
                             if self.bias else 0)

    @property
    def head_weights(self) -> int:
        return self.d * self.V

    @property
    def kv_token_bytes(self) -> int:
        """K and V of one token over every layer."""

        return self.L * 2 * self.Hkv * self.hd * self.kv_bytes

    def stack_bytes(self, head: bool) -> int:
        """Weights a step reads once: every layer, the final norm, and the
        head where logits are taken (embedding rows are negligible)."""

        n = self.L * (self.layer_weights + self.layer_other) + self.d
        if head:
            n += self.head_weights
        return n * self.w_bytes

    def token_flops(self, keys: int, head: bool) -> float:
        """One token through every layer, attending ``keys`` keys."""

        fl = self.L * (2 * self.layer_weights + 4 * self.H * self.hd * keys)
        return fl + (2 * self.head_weights if head else 0)

    def decode(self, keys: list[int]) -> tuple[float, float]:
        """(flops, bytes) of a decode step: one token per listed slot,
        each attending ``keys`` keys (its own new one included)."""

        if not keys:
            return 0.0, 0.0
        flops = sum(self.token_flops(k, True) for k in keys)
        byts = (self.stack_bytes(True)
                + self.kv_token_bytes * (sum(keys) + len(keys)))
        return float(flops), float(byts)

    def prefill(self, chunks: list[tuple[int, int, bool]]
                ) -> tuple[float, float]:
        """(flops, bytes) of a chunked-prefill step: per slot, ``n`` valid
        tokens from position ``start``; logits only where ``emits``."""

        if not chunks:
            return 0.0, 0.0
        flops = 0.0
        keys = 0
        head = False
        for start, n, emits in chunks:
            # token t attends start + t + 1 keys: the sum over the chunk
            attend = n * start + n * (n + 1) // 2
            flops += self.L * (2 * self.layer_weights * n
                               + 4 * self.H * self.hd * attend)
            if emits:
                flops += 2 * self.head_weights
                head = True
            keys += start + n
        byts = (self.stack_bytes(head)
                + self.kv_token_bytes * (keys + sum(n for _, n, _ in chunks)))
        return float(flops), float(byts)


def least_time(flops: float, byts: float, peaks: dict) -> float:
    """The larger of compute time at peak and memory time at peak."""

    return max(flops / peaks["bf16_flops_s"], byts / peaks["hbm_bytes_s"])
