"""The routed experts of an MoE layer's share in a decode step
(``kernels/moe_experts.py``, the Mosaic call ``moe_experts``).

Its work is that of the routed (token, choice) pairs alone: each pair is
one token through one SwiGLU expert, 2 * 3 * d * ff operations; each
expert that at least one pair reached is read once, 3 * d * ff * itemsize
bytes, and each pair's token row is read and its output row written once,
2 * d * itemsize. The counts come from the decode step itself
(``engine.step``'s ``moe_pairs`` and ``moe_experts_hit``, over all MoE
layers). The kernel computes every held expert over every token and
zeroes what was not routed, so it shows below its roofline by the work it
does beyond that. Prefill chunks run the same sum as ``moe_experts_chunk``,
which is not claimed here. Bound by memory at a decode step's load."""


def matches(op: str) -> bool:
    """Whether a device op (its HLO text) is this kernel: the instruction
    ``moe_experts.N`` (not ``moe_experts_chunk``, and not the ops that
    take its result as an operand)."""
    name = op.split(" = ", 1)[0].split()[-1].lstrip("%")
    return name == "moe_experts" or name.startswith("moe_experts.")


def work(pairs: int, experts_hit: int, d: int, ff: int, itemsize: int = 2):
    """(ops, bytes) of the routed experts of one decode step, over all
    its MoE layers: ``pairs`` routed (token, choice) pairs that reached
    ``experts_hit`` (layer, expert) pairs."""
    ops = 6 * d * ff * pairs
    nbytes = 3 * d * ff * itemsize * experts_hit + 2 * d * itemsize * pairs
    return ops, nbytes
