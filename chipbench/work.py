"""The work a serving round needs, counted from the configuration and the
shapes, and the chip's peaks.

Counts are of *needed* work, not of what the program happens to compute:

- prefill of B prompts of P tokens: every linear weight once per token,
  causal attention over P²/2 query-key pairs, and the LM head for the last
  position only;
- one decode step at position t (t tokens already in the cache): every
  linear weight once per sequence, attention over t + 1 keys, the LM head
  for the one new position.

Bytes are the least the chip must move: every weight byte read once (the
embedding as the rows gathered), the KV of the positions already filled
read once, and the new KV written once. Activations are not counted.
A program that does more (full S² scores, a head over every position, the
whole cache width) reads below 100 % of its roofline, never above.

``dims`` is the dict the configuration's reference gives (`dims(conf)`):
d_model, n_layers, n_heads, n_kv_heads, head_dim, d_ff, vocab,
tie_embeddings, bytes_per_param."""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)


def layer_params(dims: dict) -> int:
    """Linear weights of one layer: Q, K, V, O and the gated MLP."""
    d, hd = dims["d_model"], dims["head_dim"]
    nq, nkv = dims["n_heads"] * hd, dims["n_kv_heads"] * hd
    return d * (nq + 2 * nkv) + nq * d + 3 * d * dims["d_ff"]


def head_params(dims: dict) -> int:
    return dims["d_model"] * dims["vocab"]


def kv_bytes_per_token(dims: dict) -> int:
    """K and V of one position over every layer."""
    return (dims["n_layers"] * 2 * dims["n_kv_heads"] * dims["head_dim"]
            * dims["bytes_per_param"])


def _weight_bytes(dims: dict) -> int:
    """Layer weights plus the head matrix, each read once."""
    n = dims["n_layers"] * layer_params(dims) + head_params(dims)
    return n * dims["bytes_per_param"]


def _attn_flops(dims: dict, batch: int, pairs: float) -> float:
    """QKᵀ and AV over ``pairs`` query-key pairs per sequence, every
    layer and head: 2 products × 2 FLOPs per multiply-add."""
    return (4.0 * batch * dims["n_heads"] * dims["head_dim"] * pairs
            * dims["n_layers"])


def prefill_work(dims: dict, batch: int, prompt: int) -> Work:
    tokens = batch * prompt
    flops = (2.0 * tokens * dims["n_layers"] * layer_params(dims)
             + _attn_flops(dims, batch, prompt * prompt / 2.0)
             + 2.0 * batch * head_params(dims))
    nbytes = (_weight_bytes(dims)
              + tokens * dims["d_model"] * dims["bytes_per_param"]
              + tokens * kv_bytes_per_token(dims))
    return Work(flops, float(nbytes))


def decode_step_work(dims: dict, batch: int, filled: int) -> Work:
    """One decode step with ``filled`` positions already in the cache."""
    flops = (2.0 * batch * dims["n_layers"] * layer_params(dims)
             + _attn_flops(dims, batch, filled + 1)
             + 2.0 * batch * head_params(dims))
    nbytes = (_weight_bytes(dims)
              + batch * dims["d_model"] * dims["bytes_per_param"]
              + batch * (filled + 1) * kv_bytes_per_token(dims))
    return Work(flops, float(nbytes))


def decode_round_work(dims: dict, batch: int, prompt: int,
                      steps: int) -> Work:
    """The ``steps`` decode steps that follow a prefill of ``prompt``."""
    total = Work(0.0, 0.0)
    for i in range(steps):
        total = total + decode_step_work(dims, batch, prompt + i)
    return total


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def roofline_s(work: Work, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take for ``work``, and which peak bounds
    it ("compute" or "memory")."""
    t_flops = work.flops / peaks["bf16_flop_per_s"]
    t_bytes = work.bytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
