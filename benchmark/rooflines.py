"""Operations and bytes a call across the encoder boundary needs, from
its shapes alone, and the least time a chip could take for them. The
same whatever implements the product: a later kernel is held to the
same count.

One GF(2^8) matrix product of an (out_rows x in_rows) matrix with
in_rows streams of ``length`` bytes:

  bytes  (in_rows + out_rows) * length   each input byte read once from
                                         HBM, each output byte written
                                         once (the matrix is a few
                                         hundred bytes: left out; CRC
                                         words are not produced by the
                                         programs the served path calls
                                         today, so they are not counted)
  ops    2 * in_rows * out_rows * length one multiply and one add in the
                                         field per coefficient and byte,
                                         held against the chip's int8
                                         peak (a byte-wide operation)

The XOR parity of a xorN goal, N streams of ``length`` bytes into one:

  bytes  (N + 1) * length   N parts read, one written
  ops    (N - 1) * length   one XOR a byte for each part past the first,
                            held against the int8 peak: always
                            bytes-bound

Least time = max(bytes / HBM bytes per second, ops / int8 ops per
second); ``bound`` says which of the two it is.
"""

from __future__ import annotations


def gf_product_cost(in_rows: int, out_rows: int, length: int) -> tuple[int, int]:
    """(bytes, ops) of one product."""
    return ((in_rows + out_rows) * length, 2 * in_rows * out_rows * length)


def xor_cost(n_in: int, length: int) -> tuple[int, int]:
    """(bytes, ops) of one XOR parity of ``n_in`` parts."""
    return (n_in + 1) * length, (n_in - 1) * length


def least_seconds(nbytes: int, ops: int, peaks: dict) -> tuple[float, str]:
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["int8_ops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")


def roofline_share_pct(calls: list[tuple], device_seconds: float,
                       peaks: dict, cost=gf_product_cost
                       ) -> tuple[float, str] | None:
    """Share of the roofline of a set of calls, each the arguments of
    ``cost`` (a product's (in_rows, out_rows, length) by default), that
    together took ``device_seconds`` on the device. None where there is
    nothing to read."""
    if not calls or device_seconds <= 0:
        return None
    nbytes = sum(cost(*c)[0] for c in calls)
    ops = sum(cost(*c)[1] for c in calls)
    least, bound = least_seconds(nbytes, ops, peaks)
    return 100.0 * least / device_seconds, bound
