"""Paged latent-attention decode (``kernels/mla_decode.py``, the Mosaic
call ``mla_decode``).

One call serves one layer for every slot, in the absorbed form: each of
the ``heads`` queries scores a cached position by one dot with its latent
row (``rank`` latent + ``rope`` RoPE key) and sums the rows' latents. Its
work is that of the live positions alone: for ``t`` cached positions in
all, 2 * t * heads * (rank + rope) operations for the scores and 2 * t *
heads * rank for the weighted sum; the rows are read once, t * (rank +
rope) * itemsize bytes (the row's zero padding to whole 128-lane tiles is
not charged); the queries and the fresh rows are read and the float32
output written once. Pages that hold nothing live are not charged, so a
kernel that reads more than the live rows shows below its roofline.
Bound by memory."""


def _own_name(op: str) -> str:
    """The instruction's own name in an HLO text (``%name.N = ...``)."""
    return op.split(" = ", 1)[0].split()[-1].lstrip("%")


def matches(op: str) -> bool:
    """Whether a device op (its HLO text) is this kernel: the custom call
    is the instruction ``mla_decode.N``. Ops that take its result name it
    only as an operand, and are not claimed."""
    name = _own_name(op)
    return name == "mla_decode" or name.startswith("mla_decode.")


def work(contexts, heads: int, rank: int, rope: int, itemsize: int = 2):
    """(ops, bytes) of one call; ``contexts`` holds each decoding slot's
    number of cached positions."""
    t = sum(int(x) for x in contexts)
    b = len(contexts)
    row = rank + rope
    ops = 2 * t * heads * row + 2 * t * heads * rank
    nbytes = (t * row * itemsize + b * heads * row * itemsize
              + b * row * itemsize + b * heads * rank * 4)
    return ops, nbytes
