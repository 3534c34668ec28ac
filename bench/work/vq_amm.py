"""Fused VQ assignment + LUT accumulation (``kernels/fused_amm.py``).

For x (M, K) cut into K/v sub-vectors, c centroids per sub-vector and a
(K/v, c, N) table: distances 2*M*K*c (a multiply and an add per element of
each sub-vector against each centroid), plus one accumulate per lookup,
M*(K/v)*N. Bytes: the table (K/v)*c*N*itemsize, its (N,) float32 scale,
the (K/v, c, v) bf16 centroids, the bf16 activations and the float32
output, each once. Bound by memory at every M the engine uses."""



def matches(op: str) -> bool:
    """Whether a device op (its HLO text) is this kernel: the custom call
    is named after its jitted wrapper, ``vq_amm_pallas``."""
    return "vq_amm_pallas" in op or "_fused_kernel" in op


def work(m: int, k: int, n: int, v: int, c: int, table_itemsize: int = 1,
         act_itemsize: int = 2):
    """(ops, bytes) of one call."""
    ops = 2 * m * k * c + m * (k // v) * n
    nbytes = ((k // v) * c * n * table_itemsize + 4 * n + k * c * 2
              + m * k * act_itemsize + m * n * 4)
    return ops, nbytes
