"""Operation and byte counts computed from shapes, independent of the
implementation that does the work."""

from __future__ import annotations

from typing import Sequence

# Dormand-Prince 5(4): stage matrix, solution and error weights (nonzero
# pattern is all the byte count needs; values for the reference solver)
DOPRI5_A = (
    (),
    (1 / 5,),
    (3 / 40, 9 / 40),
    (44 / 45, -56 / 15, 32 / 9),
    (19372 / 6561, -25360 / 2187, 64448 / 6561, -212 / 729),
    (9017 / 3168, -355 / 33, 46732 / 5247, 49 / 176, -5103 / 18656),
    (35 / 384, 0.0, 500 / 1113, 125 / 192, -2187 / 6784, 11 / 84),
)
DOPRI5_B = (35 / 384, 0.0, 500 / 1113, 125 / 192, -2187 / 6784, 11 / 84, 0.0)
DOPRI5_E = (35 / 384 - 5179 / 57600, 0.0, 500 / 1113 - 7571 / 16695,
            125 / 192 - 393 / 640, -2187 / 6784 + 92097 / 339200,
            11 / 84 - 187 / 2100, -1 / 40)
DOPRI5_C = (0.0, 1 / 5, 3 / 10, 4 / 5, 8 / 9, 1.0, 1.0)

# Heun-Euler 2(1): advance with Heun, error against Euler
HEUN_EULER_A = ((), (1.0,))
HEUN_EULER_B = (0.5, 0.5)
HEUN_EULER_E = (-0.5, 0.5)
HEUN_EULER_C = (0.0, 1.0)


def rk_trial_elements(a: Sequence[Sequence[float]], b: Sequence[float],
                      e: Sequence[float]) -> int:
    """State-sized reads and writes one RK trial's stage combinations
    need, with the field evaluations counted elsewhere.

    Stage i (i >= 1) reads z and every k_j with a nonzero a_ij and writes
    z_i; the final combination reads z and every k_j with a nonzero
    solution or error weight and writes z_next.
    """
    n = 0
    for row in a[1:]:
        n += 1 + sum(1 for x in row if x != 0.0) + 1
    n += 1 + sum(1 for bi, ei in zip(b, e) if bi != 0.0 or ei != 0.0) + 1
    return n


def rk_bytes(row_trials: int, width: int, itemsize: int, a, b, e) -> int:
    """Bytes the stage combinations of ``row_trials`` single-row trials
    over a state of ``width`` elements must move."""
    return rk_trial_elements(a, b, e) * width * itemsize * int(row_trials)


def dense_lm_train_flops(n_layers: int, d_model: int, d_ff: int,
                         n_heads: int, head_dim: int, n_kv_heads: int,
                         vocab: int, seq: int, gated: bool = True) -> float:
    """Forward + backward FLOPs per token of the discrete residual stack
    of the same widths: 6 per matmul parameter, plus causal attention
    (scores and values, half the square, 3x for forward and backward)."""
    attn_w = d_model * head_dim * (2 * n_heads + 2 * n_kv_heads)
    ffn_w = d_model * d_ff * (3 if gated else 2)
    n_matmul = n_layers * (attn_w + ffn_w) + d_model * vocab
    attn = n_layers * 3 * 2 * 2 * n_heads * head_dim * seq / 2
    return 6.0 * n_matmul + attn
