"""GF(2^8) and Reed-Solomon, written out plainly for the benchmark.

The plain reference of the erasure code the deployments state:
Reed-Solomon over GF(2^8) with the reduction polynomial
x^8 + x^4 + x^3 + x^2 + 1 (0x11d) and generator 2, systematic, with
the Vandermonde parity rows Intel ISA-L's ``gf_gen_rs_matrix`` builds
(parity row r holds (2^r)^j in column j) — the code upstream LizardFS
uses for ``$ec(k,m)`` with m <= 4. numpy only; imports nothing of the
program under test.
"""

from __future__ import annotations

import numpy as np

POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(510, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[0:255]
    return exp, log


EXP, LOG = _tables()


def mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def mul_row(c: int) -> np.ndarray:
    """The 256-entry table x -> c*x."""
    out = np.zeros(256, dtype=np.uint8)
    if c:
        nz = np.arange(1, 256)
        out[1:] = EXP[LOG[nz] + LOG[c]]
    return out


def parity_rows(k: int, m: int) -> np.ndarray:
    """(m, k) parity rows of the systematic generator matrix."""
    if m > 4:
        raise ValueError("Vandermonde rows are upstream's choice for m <= 4 only")
    rows = np.zeros((m, k), dtype=np.uint8)
    gen = 1
    for r in range(m):
        p = 1
        for j in range(k):
            rows[r, j] = p
            p = mul(p, gen)
        gen = mul(gen, 2)
    return rows


def generator(k: int, m: int) -> np.ndarray:
    return np.concatenate([np.eye(k, dtype=np.uint8), parity_rows(k, m)])


def invert(mat: np.ndarray) -> np.ndarray:
    """Gauss-Jordan inverse of a square matrix over the field."""
    n = mat.shape[0]
    a = [[int(v) for v in row] for row in mat]
    out = [[int(i == j) for j in range(n)] for i in range(n)]
    for col in range(n):
        piv = next((r for r in range(col, n) if a[r][col]), None)
        if piv is None:
            raise ValueError("singular matrix")
        a[col], a[piv] = a[piv], a[col]
        out[col], out[piv] = out[piv], out[col]
        scale = inv(a[col][col])
        a[col] = [mul(v, scale) for v in a[col]]
        out[col] = [mul(v, scale) for v in out[col]]
        for r in range(n):
            if r != col and a[r][col]:
                f = a[r][col]
                a[r] = [v ^ mul(f, w) for v, w in zip(a[r], a[col])]
                out[r] = [v ^ mul(f, w) for v, w in zip(out[r], out[col])]
    return np.array(out, dtype=np.uint8)


def apply(matrix: np.ndarray, parts: list[np.ndarray]) -> list[np.ndarray]:
    """rows of ``matrix`` times the stacked ``parts`` (equal-length
    uint8 streams): one table lookup and one XOR per coefficient."""
    out = []
    for row in matrix:
        acc = np.zeros(len(parts[0]), dtype=np.uint8)
        for c, p in zip(row, parts):
            c = int(c)
            if c == 1:
                acc ^= p
            elif c:
                acc ^= mul_row(c)[p]
        out.append(acc)
    return out


def encode(k: int, m: int, data_parts: list[np.ndarray]) -> list[np.ndarray]:
    return apply(parity_rows(k, m), data_parts)


def recover(k: int, m: int, have: dict[int, np.ndarray],
            wanted: list[int]) -> dict[int, np.ndarray]:
    """Rebuild the ``wanted`` parts (global indices, data or parity)
    from any k of the parts in ``have``."""
    use = sorted(have)[:k]
    if len(use) < k:
        raise ValueError(f"need {k} parts, have {len(use)}")
    gen = generator(k, m)
    dec = invert(gen[use])          # data = dec @ have[use]
    rows = np.zeros((len(wanted), k), dtype=np.uint8)
    for i, w in enumerate(wanted):  # wanted = gen[w] @ data
        for j in range(k):
            acc = 0
            for t in range(k):
                acc ^= mul(int(gen[w, t]), int(dec[t, j]))
            rows[i, j] = acc
    got = apply(rows, [have[u] for u in use])
    return dict(zip(wanted, got))
