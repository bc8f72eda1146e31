"""Operations and bytes the algorithms need, computed from shapes.

Counts are of the work the method requires, in float32 (4 bytes), not of
what an implementation happens to do: padding, recomputation and the extra
MXU passes of a float32 matmul at HIGHEST precision are left out, so a
share of a peak computed from them can only understate the truth, never
pass 100%. One multiply-add is two operations.
"""
from __future__ import annotations

F32 = 4


def rbf(n: int, m: int, d: int) -> tuple[float, float]:
    """(ops, bytes) of one SE covariance block k(X, Z), X (n, d), Z (m, d):
    the cross term (2d per entry), the distance, scale and exp (7 per
    entry); both inputs read once, the (n, m) output written once."""
    ops = n * m * (2.0 * d + 7.0)
    nbytes = F32 * (n * d + m * d + n * m)
    return ops, float(nbytes)


def rbf_ppitc_fit(n: int, M: int, s: int, d: int) -> tuple[float, float]:
    """The covariance blocks of one pPITC fit: per block K_SD and K_DD,
    and K_SS (the program builds it twice: once for the blocks' shared
    factor, once for the store)."""
    b = n // M
    parts = [rbf(s, b, d), rbf(b, b, d)]
    ops = M * sum(p[0] for p in parts) + 2 * rbf(s, s, d)[0]
    nbytes = M * sum(p[1] for p in parts) + 2 * rbf(s, s, d)[1]
    return ops, nbytes


def ppitc_fit(n: int, M: int, s: int, d: int) -> float:
    """Operations of one whitened pPITC fit (eqs. 3-6), b = n / M:
    per block K_SD, V = L⁻¹K_SD (s²b), K_DD, VᵀV (2b²s), chol Σ_{DD|S}
    (b³/3), G = V C_L⁻ᵀ (b²s), G Gᵀ (2s²b), C⁻¹y and V·Wy (O(b²+sb));
    globally K_SS, two |S| Choleskys (K_SS and A) and the weights' solves."""
    b = n // M
    per_block = (rbf(s, b, d)[0] + s * s * b + rbf(b, b, d)[0]
                 + 2.0 * b * b * s + b ** 3 / 3.0 + b * b * s
                 + 2.0 * s * s * b + 2.0 * b * b + 2.0 * s * b)
    glob = rbf(s, s, d)[0] + 2.0 * s ** 3 / 3.0 + 2.0 * s * s
    return M * per_block + glob


def pitc_query(s: int, d: int) -> float:
    """Operations of one served pPITC query (eqs. 7-8, diagonal): the
    cross-covariance row k(x, S), two |S| triangular solves (s² each),
    the mean's dot product and the two squared norms of the variance."""
    return rbf(1, s, d)[0] + 2.0 * s * s + 6.0 * s
