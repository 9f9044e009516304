"""The attention projections as PR 39 left them, kept for the tests
alone: `wq` / `wk` / `wv` / `w_ogate` stored in-major, [L, D, heads,
Dh], and contracted `...k,kn->...n`. PR 41 stores them out-major,
[L, heads, Dh, D] (`llama._proj`: the order the decode step's dot
reads them in, so no program re-lays them out), and promised the same
numbers: to the bit in bfloat16, and in float32 up to the order of a
sum (XLA's CPU dot adds a one-row product's terms in another order).
`parent_form()` runs the program on the parent's form of the dots, so
a test can hold the two against each other and a digest read on an
older tree stays readable. Nothing of the program imports it.
"""

import contextlib

import jax.numpy as jnp

from ome_tpu.models import llama
from ome_tpu.models.quant import QTensor


def in_major(w):
    """[.., heads, Dh, D] -> [.., D, heads, Dh]: a stored leaf in the
    parent's order (the inverse of `llama.to_out_major`)."""
    return jnp.moveaxis(w, -1, -3)


def stored(name, leaf):
    """A leaf drawn in the parent's order (a benchmark reference's
    `init_weights`) in the order the program stores it."""
    return llama.to_out_major(leaf) if name in llama.OUT_MAJOR else leaf


@contextlib.contextmanager
def parent_form():
    """`llama._proj` as the parent computed it: an out-major leaf is
    put back [D, heads, Dh] (an int8 / int4 leaf dequantized first:
    its scales span the same values either way) and contracted
    in-major."""
    proj = llama._proj

    def parent_proj(x, w, dtype, out_dims=None, flatten=1,
                    out_major=False):
        if out_major:
            if isinstance(w, QTensor):
                w = w.dequant(dtype or jnp.bfloat16)
            w = in_major(w)
        return proj(x, w, dtype, out_dims, flatten)

    llama._proj = parent_proj
    try:
        yield
    finally:
        llama._proj = proj
