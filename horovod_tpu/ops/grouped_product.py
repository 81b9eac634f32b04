"""Grouped products of an expert layer's sized rows at widths that are
not whole lane tiles: two Mosaic kernels after JAX's megablox.

``parallel/moe.py`` multiplies the rows of each held expert's group by
that expert's matrix with ``jax.lax.ragged_dot``, which the TPU compiler
expands into a grouped Mosaic kernel of its own. That kernel tiles the
matrices' two widths by what divides them. An expert 1856 wide (14.5
lane tiles of 128) on a hidden size of 2688 (21) leaves it small tiles:
one product of 6,144 drawn rows in 8 groups took 3.0-3.3 ms where the
same rows against one matrix take 0.40, 6.6 ms under another draw of
the same size, and 0.89 with both widths padded to 3072 and 2048 (my
chip runs, PR 48; PERF.md section 6). The time of the step then followed
the seed's draw, not the work.

The kernels here follow ``jax.experimental.pallas.ops.tpu.megablox``
(whose ``make_group_metadata`` plans their grids; its own calls state no
varying-axes type for their results, which a call inside ``shard_map``
must): ``hvd_moe_gmm``, rows by group against ``[groups, k, n]`` or its
transpose, and ``hvd_moe_tgmm``, each group's rows of one operand
against the same rows of another, contracted over the rows as they lie
(no transpose in HBM). A grid step is one tile of ``_ROWS`` rows and one
group that has rows in it; a tile two groups share is visited once for
each, its other rows masked. A width is one block, or cut into whole
lane tiles that divide it, so nothing is padded in HBM. No tile past the
groups is visited, as in XLA's kernel: what is left in rows past the
groups is not specified either, and the time still follows the draw,
0.09 ms a thousand rows a product. At the shapes above megablox's calls
took 0.58-0.78 ms for each of the four products to rows and 1.38 and
0.80 for the two to the weights, a transpose of the left operand (0.24)
included (my chip runs, PR 48).

``takes`` decides from shapes alone, and off the TPU (where
``flash_attention._interpret()`` holds) nothing is taken: the caller
keeps ``lax.ragged_dot``, as it does for every width in whole tiles.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention

# ``megablox/__init__`` names its differentiable wrappers ``gmm`` too,
# over the module that holds the planner.
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

# The kernels' names in a device trace (docs/tracing.md), under the
# caller's scope ``hvd_moe/experts``: readers match the literals.
KERNEL_ROWS = "hvd_moe_gmm"
KERNEL_WEIGHTS = "hvd_moe_tgmm"

_LANES = 128
_ROWS = 256             # rows a tile of the products to rows (512: 8% slower)
_ROWS_WEIGHTS = 512     # and of the products to the weights
_WHOLE = 2048           # a width up to this is one block
_MOST = 1024            # else the largest whole-tile divisor up to this
# Numbers in a block of the weights' gradient: the kernel holds it three
# times in float32 (twice the result, once the sum).
_WEIGHTS_BLOCK = 896 * 1024
_VMEM = 16 * 2 ** 20    # what the blocks of one call may take


def _interpret():
    """Compiled wherever the flash kernels are: a test that steers the
    step onto the TPU's path steers all of it."""
    return flash_attention._interpret()


def _block(width):
    """A width's block: all of it up to ``_WHOLE``, else its largest
    divisor in whole lane tiles up to ``_MOST``; None where there is
    none."""
    if width <= _WHOLE:
        return width
    return next((b for b in range(_MOST, 0, -_LANES) if width % b == 0),
                None)


def takes(rows, hidden, width, dtype):
    """True where these kernels take an expert layer's grouped products:
    on the TPU, bfloat16 rows in whole row tiles, a width that is not
    whole lane tiles (XLA's grouped kernel keeps the others), and
    blocks that VMEM holds twice beside the float32 sum."""
    blocks = _block(hidden), _block(width)
    if (_interpret() or jnp.dtype(dtype) != jnp.bfloat16
            or rows % _ROWS_WEIGHTS or None in blocks
            or not (hidden % _LANES or width % _LANES)):
        return False
    k, n = blocks
    return 4 * (_ROWS * (k + n) + k * n) + 4 * _ROWS * max(k, n) <= _VMEM


def _plan(sizes, rows, tile, empty_too):
    """``(offsets (groups + 1,), group_of, tile_of (steps at most,))``
    and the steps there are: a step is a tile of ``tile`` rows and a
    group with rows in it (or, where ``empty_too``, with none: its
    result is still to be zeroed), tiles in order, no tile past the
    groups."""
    return _megablox.make_group_metadata(
        group_sizes=sizes, m=rows, tm=tile, start_group=jnp.int32(0),
        num_nonzero_groups=sizes.shape[0], visit_empty_groups=empty_too)


def _mine(offsets, group_of, tile_of, step, tile, shape, axis):
    """Which rows of this step's tile are its group's, along ``axis``
    of ``shape``."""
    row = tile_of[step] * tile + lax.broadcasted_iota(jnp.int32, shape, axis)
    group = group_of[step]
    return (row >= offsets[group]) & (row < offsets[group + 1])


def _rows_kernel(offsets, group_of, tile_of, a_ref, w_ref, out_ref, total, *,
                 transposed):
    step, part = pl.program_id(1), pl.program_id(2)

    @pl.when(part == 0)
    def _():
        total[...] = jnp.zeros_like(total)
    total[...] += lax.dot_general(
        a_ref[...], w_ref[...],
        (((1,), (1 if transposed else 0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(part == pl.num_programs(2) - 1)
    def _():
        # A tile that the group before shares keeps that group's rows:
        # its block is still the one in VMEM.
        mine = _mine(offsets, group_of, tile_of, step, total.shape[0],
                     total.shape, 0)
        out_ref[...] = jnp.where(
            mine, total[...], out_ref[...].astype(jnp.float32)).astype(
                out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("transposed", "interpret"))
def _rows_call(a, w, sizes, *, transposed, interpret):
    rows = a.shape[0]
    k, n = w.shape[1:][::-1] if transposed else w.shape[1:]
    tk, tn = _block(k), _block(n)
    plan, steps = _plan(sizes, rows, _ROWS, False)
    return pl.pallas_call(
        functools.partial(_rows_kernel, transposed=transposed),
        out_shape=flash_attention._struct((rows, n), a.dtype, a, w, sizes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, steps, k // tk),
            in_specs=[
                pl.BlockSpec((_ROWS, tk),
                             lambda j, i, p, _, __, tile_of: (tile_of[i], p)),
                pl.BlockSpec(
                    (None, tn, tk) if transposed else (None, tk, tn),
                    (lambda j, i, p, _, group_of, __: (group_of[i], j, p))
                    if transposed else
                    (lambda j, i, p, _, group_of, __: (group_of[i], p, j)))],
            out_specs=pl.BlockSpec(
                (_ROWS, tn), lambda j, i, p, _, __, tile_of: (tile_of[i], j)),
            scratch_shapes=[pltpu.VMEM((_ROWS, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name=KERNEL_ROWS)(*plan, a, w)


def rows_by_group(a, w, sizes, transposed=False):
    """``a[group g's rows] @ w[g]`` (``@ w[g].T`` where ``transposed``):
    ``a`` (rows, k), ``w`` (groups, k, n) or (groups, n, k), ``sizes``
    (groups,) int32; (rows, n) in ``a``'s dtype, summed in float32.
    Rows in whole tiles of ``_ROWS``, widths that ``_block`` divides."""
    return _rows_call(a, w, sizes, transposed=transposed,
                      interpret=_interpret())


def _weights_kernel(offsets, group_of, tile_of, a_ref, ct_ref, out_ref, total):
    step, steps = pl.program_id(2), pl.num_programs(2)
    group = group_of[step]

    @pl.when((step == 0) | (group != group_of[jnp.maximum(step - 1, 0)]))
    def _():
        total[...] = jnp.zeros_like(total)
    # Other groups' rows of the tile, and rows past every group, whose
    # content is not specified: selected away on both sides.
    a, ct = (jnp.where(_mine(offsets, group_of, tile_of, step, ref.shape[0],
                             ref.shape, 0), ref[...], 0)
             for ref in (a_ref, ct_ref))
    total[...] += lax.dot_general(a, ct, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    @pl.when((step == steps - 1)
             | (group != group_of[jnp.minimum(step + 1, steps - 1)]))
    def _():
        out_ref[...] = total[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _weights_call(a, ct, sizes, *, interpret):
    rows, k, n = a.shape[0], a.shape[1], ct.shape[1]
    tk = _block(k)
    tn = min(_block(n), _WEIGHTS_BLOCK // tk // _LANES * _LANES)
    plan, steps = _plan(sizes, rows, _ROWS_WEIGHTS, True)
    return pl.pallas_call(
        _weights_kernel,
        out_shape=flash_attention._struct(
            (sizes.shape[0], k, n), jnp.float32, a, ct, sizes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(-(-n // tn), k // tk, steps),
            in_specs=[
                pl.BlockSpec((_ROWS_WEIGHTS, tk),
                             lambda j, p, i, _, __, tile_of: (tile_of[i], p)),
                pl.BlockSpec((_ROWS_WEIGHTS, tn),
                             lambda j, p, i, _, __, tile_of: (tile_of[i], j))],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda j, p, i, _, group_of, __: (group_of[i], p, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=KERNEL_WEIGHTS)(*plan, a, ct)


def weights_by_group(a, ct, sizes):
    """``a[group g's rows].T @ ct[the same rows]`` for each group:
    ``a`` (rows, k), ``ct`` (rows, n); (groups, k, n) float32, zeros for
    a group with no rows."""
    return _weights_call(a, ct, sizes, interpret=_interpret())
