"""The work a serving round of a Mamba-2/attention hybrid needs, counted
from the configuration's sizes (`reference/granite_hybrid.py` ``dims``).

Counts are of *needed* work, as `work.py` counts a dense decoder's:

- bytes: every weight byte read once (the matrices and norms in bf16,
  ``dt_bias``, ``A_log`` and ``D`` in float32; the tied head once, and the
  embedding as the rows gathered); each Mamba-2 layer's SSM state and conv
  state read once and written once per step, at the dtypes the
  configuration file states; the attention layers' filled KV read once
  and the new KV written once. Activations are not counted.
- FLOPs: every linear weight once per token, 2 per multiply-add; the
  depthwise conv (2·K per channel and token); the recurrence in its
  cheapest, sequential form, per token and head: decay the state (P·N),
  form dt·x ⊗ B (P + P·N), add (P·N), read it out with C (2·P·N) and add
  D·x (2·P), so 5·P·N + 3·P; causal attention in the attention layers
  (QKᵀ and AV, t + 1 keys in decode, P²/2 pairs in prefill); the head at
  the last position only.

The program's prefill runs the chunked SSD form, which does more than the
sequential recurrence, and its head may run over every prompt position;
so a reading of a roofline share stays below 100 %."""

from __future__ import annotations

from chipbench.work import Work

F32_BYTES = 4


def n_kinds(dims: dict) -> tuple[int, int]:
    """(Mamba-2 layers, attention layers)."""
    types = dims["layer_types"]
    n_m = sum(1 for t in types if t == "mamba")
    return n_m, len(types) - n_m


def _ssm(dims: dict) -> tuple[int, int, int, int]:
    """(H, P, N, channels of the conv: di + 2·G·N)."""
    H, P, N = dims["ssm_heads"], dims["ssm_head_dim"], dims["ssm_state"]
    return H, P, N, H * P + 2 * dims["ssm_groups"] * N


def mamba_matrix_params(dims: dict) -> int:
    """Linear weights of one Mamba-2 mixer: in_proj (z, xBC, dt) and
    out_proj."""
    d = dims["d_model"]
    H, P, _, C = _ssm(dims)
    return d * (H * P + C + H) + H * P * d


def attn_matrix_params(dims: dict) -> int:
    """Q, K, V and O of one attention layer."""
    d, hd = dims["d_model"], dims["head_dim"]
    nq, nkv = dims["n_heads"] * hd, dims["n_kv_heads"] * hd
    return d * (nq + 2 * nkv) + nq * d


def mlp_params(dims: dict) -> int:
    return 3 * dims["d_model"] * dims["d_ff"]


def head_params(dims: dict) -> int:
    return dims["d_model"] * dims["vocab"]


def weight_bytes(dims: dict) -> int:
    """Every weight read once: the layers' matrices, conv taps and bias,
    norms and float32 vectors, the final norm and the head."""
    d, bp = dims["d_model"], dims["bytes_per_param"]
    H, P, _, C = _ssm(dims)
    n_m, n_a = n_kinds(dims)
    mamba = ((mamba_matrix_params(dims) + dims["ssm_conv"] * C + C
              + H * P) * bp + 3 * H * F32_BYTES)
    every = (mlp_params(dims) + 2 * d) * bp          # MLP, norm1, norm2
    return (n_m * mamba + n_a * attn_matrix_params(dims) * bp
            + dims["n_layers"] * every + (d + head_params(dims)) * bp)


def state_bytes_per_request(dims: dict) -> int:
    """One request's SSM and conv state over every Mamba-2 layer."""
    H, P, N, C = _ssm(dims)
    n_m, _ = n_kinds(dims)
    return n_m * (H * P * N * dims["ssm_state_bytes"]
                  + (dims["ssm_conv"] - 1) * C * dims["conv_state_bytes"])


def kv_bytes_per_token(dims: dict) -> int:
    """K and V of one position over the attention layers."""
    _, n_a = n_kinds(dims)
    return (n_a * 2 * dims["n_kv_heads"] * dims["head_dim"]
            * dims["bytes_per_param"])


def _token_flops(dims: dict) -> float:
    """FLOPs of one token through every layer but attention's score and
    weighted sum, and without the head."""
    H, P, N, C = _ssm(dims)
    n_m, n_a = n_kinds(dims)
    mamba = (2.0 * mamba_matrix_params(dims) + 2.0 * dims["ssm_conv"] * C
             + H * (5.0 * P * N + 3.0 * P))
    return (n_m * mamba + n_a * 2.0 * attn_matrix_params(dims)
            + dims["n_layers"] * 2.0 * mlp_params(dims))


def _attn_flops(dims: dict, batch: int, pairs: float) -> float:
    _, n_a = n_kinds(dims)
    return 4.0 * batch * dims["n_heads"] * dims["head_dim"] * pairs * n_a


def prefill_work(dims: dict, batch: int, prompt: int) -> Work:
    """Prefill of ``batch`` prompts of ``prompt`` tokens: the decode cache
    (KV of every position, each sequence's final states) is written."""
    tokens = batch * prompt
    flops = (tokens * _token_flops(dims)
             + _attn_flops(dims, batch, prompt * prompt / 2.0)
             + 2.0 * batch * head_params(dims))
    nbytes = (weight_bytes(dims)
              + tokens * dims["d_model"] * dims["bytes_per_param"]
              + tokens * kv_bytes_per_token(dims)
              + batch * state_bytes_per_request(dims))
    return Work(flops, float(nbytes))


def decode_step_work(dims: dict, batch: int, filled: int) -> Work:
    """One decode step with ``filled`` positions already in the cache."""
    flops = (batch * _token_flops(dims)
             + _attn_flops(dims, batch, filled + 1)
             + 2.0 * batch * head_params(dims))
    nbytes = (weight_bytes(dims)
              + batch * dims["d_model"] * dims["bytes_per_param"]
              + batch * (filled + 1) * kv_bytes_per_token(dims)
              + 2 * batch * state_bytes_per_request(dims))
    return Work(flops, float(nbytes))


def decode_round_work(dims: dict, batch: int, prompt: int,
                      steps: int) -> Work:
    """The ``steps`` decode steps that follow a prefill of ``prompt``."""
    total = Work(0.0, 0.0)
    for i in range(steps):
        total = total + decode_step_work(dims, batch, prompt + i)
    return total
