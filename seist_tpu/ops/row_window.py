"""A window of a circular row, read batch-wide: ``data[:, (start + t) % L]``
for ``t < width``.

The device augmentation (``data/device_aug.py``) is written for ONE sample
and ``vmap``ped over the batch, and it reads such a window three times:
``shift_event``'s roll (``jnp.roll(data, s)`` is the window of width ``L``
from ``(L - s) % L``), ``cut_window``'s crop (``lax.dynamic_slice`` is the
window of width ``W`` from ``c_l``) and ``add_event_once``'s roll. What
``vmap`` makes of a per-row dynamic start is a gather with a different
offset in every row, which the TPU compiler expands into a ``while`` that
walks the batch ONE ROW an iteration at about 2 us each, over a padded copy
of the rows (PERF.md, PRs 30 and 36).

:func:`circular_window` is written for one row, like its callers; its
batching rule (``jax.custom_batching.custom_vmap``) issues ONE Pallas kernel
for the whole batch: a grid step a row, the rows' rotations scalar-prefetched
to SMEM, the row rotated along the lanes in VMEM by a dynamic amount. A row
of ``L`` samples lives in a buffer of ``Lp = round_up(L, 128)`` lanes, so a
rotation of the buffer wraps ``Lp - L`` lanes late: the columns before the
wrap take the rotation by ``s``, those after it the rotation by ``s + (Lp -
L)`` — a second, static rotation of the first.

Where it runs: on the TPU backend the kernel (a call outside ``vmap`` takes
the same kernel with a grid of one); on other backends the ``jnp.roll`` /
``dynamic_slice`` it replaced. It moves data and computes nothing, so the
two are bit-identical; ``interpret=True`` drives the kernel through the
Pallas interpreter in tests only. Rows of other than 4-byte elements take
the plain form too (the kernel's buffer is laid out for 8 x 128 tiles).
The kernel reads rows time along the lanes; the passes around it keep them
otherwise, so the rows are pinned to the compiler's own layout on both
sides of the call (:func:`_as_the_compiler_keeps`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

_LANES = 128
_SUBLANES = 8


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _plain(data, start, width: int):
    """The XLA form for one row (C, L) and a start in [0, L): the row twice
    over, sliced — what ``jnp.roll`` by a traced amount is made of, and
    ``dynamic_slice`` itself where the window does not wrap."""
    return lax.dynamic_slice_in_dim(
        jnp.concatenate([data, data], axis=-1), start, width, axis=-1
    )


def _kernel(shift_ref, x_ref, o_ref, buf_ref, *, length: int, width: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    channels = x_ref.shape[1]
    shift = shift_ref[pl.program_id(0)]  # jnp.roll's amount, in [0, L)
    # Rows beyond ``channels`` and lanes beyond ``length`` of the buffer are
    # never selected into the output, whatever they hold.
    buf_ref[0:channels, 0:length] = x_ref[0]
    rolled = pltpu.roll(buf_ref[...], shift, axis=1)
    late = buf_ref.shape[1] - length
    if late:
        col = lax.broadcasted_iota(jnp.int32, rolled.shape, 1)
        rolled = jnp.where(
            col >= shift, rolled, pltpu.roll(rolled, late, axis=1)
        )
    o_ref[0] = rolled[0:channels, 0:width]


def _as_the_compiler_keeps(rows):
    """Pins a full batch of ``rows`` (B, C, L) to the layout the TPU
    compiler keeps it in between elementwise passes: where the batch fills
    the lanes and ``L`` does not (256 rows of 12000 samples), the batch
    lies along the lanes and time along the sublanes. The kernel wants
    ``(B, C, L)`` row-major, time along the lanes, and the compiler hands a
    custom call's layout on to every elementwise pass around it in place of
    a copy at its door: the augmentation's dense passes then ran 2.6 times
    slower (half-filled ``T(4,128)`` tiles), more than the kernels saved,
    and summed in another order (PERF.md, PR 36). Pinned on both sides, the
    rows are re-laid at the kernel's door (0.07 ms a copy) and the passes
    stay what they were, to the bit. Rows the compiler keeps time along the
    lanes anyway (a window of 8192 samples, a small batch) flow on in the
    kernel's layout, as the output of the loop they replaced did."""
    from jax.experimental.layout import Layout, with_layout_constraint

    batch, _, length = rows.shape
    if batch % _LANES or length % _LANES == 0:
        return rows
    # major to minor: channels, time, batch
    return with_layout_constraint(rows, Layout(major_to_minor=(1, 2, 0)))


def _kernel_rows(data, start, width: int, interpret: bool):
    """The kernel over rows (B, C, L) with starts (B,), all in [0, L)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, channels, length = data.shape
    shift = (length - start) % length  # jnp.roll's amount for this window
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((1, channels, length), lambda i, s: (i, 0, 0))
        ],
        out_specs=pl.BlockSpec((1, channels, width), lambda i, s: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM(
                (_round_up(channels, _SUBLANES), _round_up(length, _LANES)),
                data.dtype,
            )
        ],
    )
    return pl.pallas_call(
        partial(_kernel, length=length, width=width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, channels, width), data.dtype),
        interpret=interpret,
        name="circular_window",  # the op's name in a device trace
    )(shift, data)


def _rows(data, start, width: int, interpret: bool):
    """:func:`_kernel_rows` laid out as the step being traced lays its rows
    out: Mosaic kernels cannot be partitioned automatically, so under a
    data-parallel mesh each device runs the kernel on ITS rows inside a
    shard_map (as ``pallas_attention._fused_rows`` does)."""
    from jax.sharding import PartitionSpec as P

    from seist_tpu.parallel import mesh as mesh_lib

    def call(data, start):
        # the interpreter runs on the CPU, where no layout turns
        pin = (lambda rows: rows) if interpret else _as_the_compiler_keeps
        return pin(_kernel_rows(pin(data), start, width, interpret))

    mesh = mesh_lib.active_mesh()
    if mesh is None or mesh.shape.get(mesh_lib.AXIS_DATA, 1) == 1:
        return call(data, start)
    rows = P(mesh_lib.AXIS_DATA)
    return jax.shard_map(
        call, mesh=mesh, in_specs=(rows, rows), out_specs=rows,
        check_vma=False,
    )(data, start)


def circular_window(data, start, width: int, *, interpret: bool = False):
    """``data[:, (start + t) % L]`` for ``t`` in ``range(width)``: ``data``
    one row (C, L), ``start`` an int32 scalar (traced; any integer, taken
    modulo ``L``), ``width`` static, at most ``L``. Under ``vmap`` the whole
    batch is one kernel on the TPU (module docstring); nothing here is
    differentiated (the augmentation runs outside the step's gradient)."""
    channels, length = data.shape
    if not 0 < width <= length:
        raise ValueError(f"window of {width} samples from a row of {length}")
    start = jnp.asarray(start, jnp.int32) % length
    if not (interpret or _on_tpu()) or data.dtype.itemsize != 4:
        return _plain(data, start, width)

    @jax.custom_batching.custom_vmap
    def window(data, start):  # one row: a grid of one, whatever the mesh
        return _kernel_rows(data[None], start[None], width, interpret)[0]

    @window.def_vmap
    def _(axis_size, in_batched, data, start):
        data_b, start_b = in_batched
        if not data_b:
            data = jnp.broadcast_to(data, (axis_size,) + data.shape)
        if not start_b:
            start = jnp.broadcast_to(start, (axis_size,))
        return _rows(data, start, width, interpret), True

    return window(data, start)
