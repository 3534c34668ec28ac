"""Paged flash-decode attention (``kernels/flash_decode.py``).

One call serves one layer for every slot. Its work is that of the live
positions alone: for a slot attending ``t`` cached positions, QK and PV
are 2 * 2 * t * heads * head_dim operations, and the keys and values of
those positions are read once, 2 * t * kv_heads * head_dim * itemsize
bytes; the queries and the output are added. Pages that hold nothing
live are not charged, so a kernel that reads the whole pool shows below
its roofline. Bound by memory."""



def matches(op: str) -> bool:
    """Whether a device op (its HLO text) is this kernel. Its custom call
    carries no name of its own (it is named after whatever jitted function
    encloses it), so it is known by its results: the three float32 arrays
    (running max, sum and output) of the split-KV pass, the only Mosaic
    call of the step programs that returns a triple."""
    if "flash_kernel" in op or "flash_decode" in op:
        return True
    head = op.split(" custom-call(", 1)
    if len(head) != 2 or 'custom_call_target="tpu_custom_call"' not in op:
        return False
    result = head[0].split(" = ", 1)[-1]
    return (result.startswith("(") and result.count("f32[") == 3
            and result.count("[") == 3)


def work(contexts, heads: int, kv_heads: int, head_dim: int,
         itemsize: int = 2):
    """(ops, bytes) of one call; ``contexts`` holds each decoding slot's
    number of cached positions."""
    t = sum(int(x) for x in contexts)
    ops = 4 * t * heads * head_dim
    nbytes = (2 * t * kv_heads * head_dim * itemsize
              + 2 * len(contexts) * heads * head_dim * itemsize)
    return ops, nbytes
