"""Operations and bytes the linear-attention layers of a hybrid decoder
(the gated delta rule behind a short causal convolution) NEED, a token,
in each of the two forms a served model computes them in, and the bytes
of weights and cache a decode step of the whole model reads.  Computed
from the configuration's keys alone and kept with the benchmark, so that
a change to the program cannot change what a share of a roofline is a
share of.  2 per multiply-add; ``m`` is a configuration file's model keys
with ``layer_types`` (the depth as cut)."""

from __future__ import annotations

#: tokens of one sub-chunk of the chunked (WY / UT transform) form: the
#: usual size, a constant of the algorithm and not of its implementation
SUB_CHUNK = 64


def layers_of(m: dict, kind: str) -> int:
    return sum(1 for t in m["layer_types"][:m["num_hidden_layers"]]
               if t == kind)


def _dims(m: dict):
    return (m["linear_num_key_heads"], m["linear_key_head_dim"],
            m["linear_value_head_dim"])


def conv_channels(m: dict) -> int:
    h, dk, dv = _dims(m)
    return h * (2 * dk + dv)


def state_bytes_per_row(m: dict, act_itemsize: int = 2) -> int:
    """Bytes of recurrent state ONE sequence holds across the linear
    layers: S in float32 (13.27 MB at the published sizes and 6 layers)
    and the convolution's last ``kernel - 1`` inputs in the activations'
    dtype (0.41 MB).  A decode step reads and writes both for each live
    row."""
    h, dk, dv = _dims(m)
    return layers_of(m, "linear_attention") * (
        h * dk * dv * 4
        + (m["linear_conv_kernel_dim"] - 1) * conv_channels(m)
        * act_itemsize)


def matrix_state_bytes_per_row(m: dict) -> int:
    """The float32 S alone (what the recurrent kernel moves)."""
    h, dk, dv = _dims(m)
    return layers_of(m, "linear_attention") * h * dk * dv * 4


def recurrent_flops_per_token(m: dict) -> int:
    """Step 5 as written, one token, all linear layers: the decay
    (``d_k d_v``), ``S k``, the rank-one update and ``S q`` (2 ``d_k d_v``
    each) a head, and the convolution's taps."""
    h, dk, dv = _dims(m)
    return layers_of(m, "linear_attention") * (
        h * 7 * dk * dv
        + 2 * m["linear_conv_kernel_dim"] * conv_channels(m))


def chunked_flops_per_token(m: dict, sub_chunk: int = SUB_CHUNK) -> int:
    """The WY / UT-transform form, a token, all linear layers.  A head and
    token: three products with the state (``v' = u - w S``, ``q S``, ``k^T
    v'``: 6 ``d_k d_v``), the sub-chunk's ``k k^T``, ``q k^T`` and ``w``
    (6 ``C d_k``), ``u`` and the intra-chunk output (4 ``C d_v``) and the
    triangular solve (``C^2``)."""
    h, dk, dv = _dims(m)
    c = sub_chunk
    return layers_of(m, "linear_attention") * (
        h * (6 * dk * dv + c * (6 * dk + 4 * dv) + c * c)
        + 2 * m["linear_conv_kernel_dim"] * conv_channels(m))


def chunked_bytes_per_token(m: dict, act_itemsize: int = 2) -> int:
    """Activations the chunked form must move a token, all linear layers:
    q, k, v in, o out, the two gates in float32 (the state is read and
    written once a launch, a thousandth of this a token)."""
    h, dk, dv = _dims(m)
    return layers_of(m, "linear_attention") * h * (
        (2 * dk + 2 * dv) * act_itemsize + 8)


def chunked_needed_seconds(m: dict, tokens: int, peaks: dict) -> float:
    return max(tokens * chunked_flops_per_token(m) / peaks["bf16_flops"],
               tokens * chunked_bytes_per_token(m)
               / peaks["hbm_bytes_per_s"])


def layer_params(m: dict, kind: str) -> int:
    """Parameters of one layer of ``kind``: the mixer, its two norms'
    gains and the SwiGLU MLP."""
    d, f = m["hidden_size"], m["intermediate_size"]
    h, dk, dv = _dims(m)
    mlp = 3 * d * f + 2 * d
    if kind == "full_attention":
        return 4 * d * d + 2 * d + mlp
    return (d * conv_channels(m)                    # q, k, v
            + m["linear_conv_kernel_dim"] * conv_channels(m)
            + 2 * d * h + 2 * h                     # a, b, A_log, dt_bias
            + 2 * d * h * dv + dv                   # gate, out, norm gain
            + mlp)


def step_weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Bytes of weights EVERY decode step reads: every layer, the final
    norm and the head (the embedding lookup reads rows only)."""
    d = m["hidden_size"]
    return itemsize * (
        sum(layers_of(m, k) * layer_params(m, k)
            for k in ("linear_attention", "full_attention"))
        + d + d * m["vocab_size"])


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Bytes of K and V one context position holds across the full
    layers: what a decode step must read for each live position."""
    return layers_of(m, "full_attention") * 2 * m["hidden_size"] * itemsize
