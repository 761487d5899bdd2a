"""The routed experts' grouped products, a chunk of tiles a launch
(docs/qwen3_next.md "What a trip of the loop does").

``layers/moe.py`` lays the (token, held expert) pairs out expert by expert in
tiles of ``tile`` rows and walks the tiles in use, a loop whose trip count is
data. Its tile loop visits one tile a trip and, every trip, slices the
expert's three matrices out of HBM again, adds the tile's weight gradient
into three whole float32 expert matrices in HBM, and hands the tile's rows to
XLA's scatter-add, which takes 0.30 us a row on a v5e whatever the rows a
call. Here a trip takes a CHUNK of ``chunk`` consecutive tiles: their rows
are gathered in XLA (0.05 us a row), and one Mosaic launch walks the chunk's
tiles with each tile's expert and the rows' tokens as scalar prefetch.

``moe_grouped_fwd``  y_t = (silu(x_t Wg_e) * (x_t Wu_e)) Wd_e in float32,
    weighted and added into the tokens' sums. The weights' block index is
    the tile's expert, so a block is fetched when the expert CHANGES, once a
    run of tiles and not once a tile.
``moe_grouped_bwd``  the forward again, then d row_w, dx_t added into the
    tokens' sums, and the three weight gradients. dWg, dWu, dWd of the
    current expert stay in VMEM (float32 scratch) over the run of its tiles;
    they leave for HBM by one DMA each when the run ends, and that DMA is
    waited for only when the next run first touches the scratch, behind its
    own forward and dx products. The gradients' arrays come in aliased to the
    results: an expert with no tile keeps the zeros it came with, and a run
    that continues the last run of the chunk before reads its sums back, once.

The tokens' float32 sums (y forward, dx backward) are carried through the
chunks in the layout of ``_RowSums`` and added to by the launches' own DMAs,
aliased in and out; ``_RowSums.summed`` brings one back to [N, d] after the
loop.

A tile past the tiles in use inside the last chunk is skipped under
``pl.when`` with every index map held at the last live tile: no byte moves
for it.

Every rounding point is the tile loop's: ``h``, ``da``, ``db`` and the
weighted ``dy`` are cast to the input's type, products accumulate in float32,
sums over a token's experts are float32 in the loop's order, the gradients
are cast once, after the loop. On a v5e the weight gradients are the tile
loop's to the bit and dx lies within 1.7e-5 of its norm: Mosaic sums a
product's float32 terms in its own order (tools/moe_grouped_probe.py).

``chunk_plan`` is the one gate: from (d, I, tile, dtype) it gives the
chunk's length (what the gathered rows may hold) and how many buffers the
weights' blocks get (two where the backward launch's VMEM estimate allows,
so that the next expert's fetch hides behind the products; one where an
expert is wide), or why the kernels do not cover the shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels._pallas_util import VMEM_LIMIT_BYTES, round_up
from paddle_tpu.kernels._pallas_util import nt as _nt, tn as _tn

# what the gathered rows of one chunk may hold, in the input's type
CHUNK_BYTES = 16 * 1024 * 1024
# what the backward launch may hold of VMEM_LIMIT_BYTES by the estimate
# below; the rest is Mosaic's own (spills, the products' staging)
_VMEM_BUDGET = VMEM_LIMIT_BYTES * 7 // 8
# rows a trip of a DMA loop handles (Mosaic unrolls a loop whole or not at
# all; on a v5e a tile's 4 x 256 DMAs take 25 us at 1 and 19 at 8 or 16)
_UNROLL = 8
# rows of a weight gradient one product makes (its float32 result is a
# temporary before it is added to the resident sum)
_DW_ROWS = 512

f32 = jnp.float32


def bwd_vmem_bytes(d, I, tile, dtype, buffers):
    """(all, gradients) bytes ``moe_grouped_bwd`` holds in VMEM: the
    expert's three float32 gradients, once; its three weight blocks
    ``buffers`` times; every tile block twice, as the pipeline keeps the next
    one coming (x, dy in; row_w in, d row_w out: a [tile, 1] column pads to
    128 lanes) and so do the two buffers dx's rows pass through; the body's
    float32 temporaries ([tile, I] a, b, their sigmoid, silu and dh, the
    roundings; [tile, d] y and dx; one weight-gradient product)."""
    item = jnp.dtype(dtype).itemsize
    grads = 3 * d * I * 4
    tiles = 2 * (2 * tile * d * item + tile * d * 4 + 2 * tile * 128 * 4)
    body = 8 * tile * I * 4 + 3 * tile * d * 4 + _DW_ROWS * max(d, I) * 4
    return grads + buffers * 3 * d * I * item + tiles + body, grads


def chunk_plan(d, I, tile, dtype):
    """((chunk, buffers), "") where the kernels cover the shape, else
    (None, why not): an expert width of whole lanes, a hidden width whose
    row in a token sum is whole float32 tiles (8 sublane rows of 128), tiles
    of whole sublane groups, bf16 or float32, and an expert whose gradients
    fit in VMEM beside its weights."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.bfloat16, jnp.float32) or d % 1024 or I % 128 \
            or tile % (32 // dtype.itemsize):
        return None, (f"widths {d} x {I}, tiles of {tile} rows, {dtype.name} "
                      "are outside the kernels' gate")
    chunk = max(1, CHUNK_BYTES // (tile * d * dtype.itemsize))
    for buffers in (2, 1):
        need = bwd_vmem_bytes(d, I, tile, dtype, buffers)[0]
        if need <= _VMEM_BUDGET:
            return (chunk, buffers), ""
    return None, (f"an expert of {d} x {I} would hold {need / 1e6:.1f} MB in "
                  f"VMEM, its gradients whole, against the "
                  f"{_VMEM_BUDGET / 1e6:.1f} MB of the kernels' gate")


def _hidden(x, wg, wu):
    """(a, b, sigmoid(a), h) of one tile: h in x's type."""
    a = jnp.dot(x, wg, preferred_element_type=f32)
    b = jnp.dot(x, wu, preferred_element_type=f32)
    sig = jax.nn.sigmoid(a)
    return a, b, sig, (a * sig * b).astype(x.dtype)


class _RowSums:
    """A float32 sum over tokens that lives in HBM as [(N + 1) * C, 128]: a
    token's row of C * 128 numbers is C whole sublane rows, 8 KB in one
    piece, so one DMA moves it; token N is a row nobody reads, where the
    padding rows' DMAs land. A tile adds its rows to the sum by DMA: the
    rows come into a VMEM buffer, the tile's values are added there (column
    block c of the [tile, C * 128] values goes to every C-th sublane row
    from c on), the rows go back. Within a tile the tokens differ; so do
    the tokens of two tiles of one expert, whose DMAs may therefore overlap;
    a tile of another expert first waits for the rows to have gone back.
    XLA's scatter-add takes 0.30 us a row of these on a v5e, the DMAs 0.1
    (PERF.md section 6, PR 35)."""

    @staticmethod
    def zeros(N, d):
        """A sum over N tokens of width d, and the row of token N."""
        return jnp.zeros(((N + 1) * (d // 128), 128), f32)

    @staticmethod
    def summed(sums, N, d, dtype):
        """The sum back as [N, d]."""
        return sums[:N * (d // 128)].reshape(N, d).astype(dtype)

    @staticmethod
    def scratch(tile, d):
        """Two buffers of a tile's rows, and the two DMA semaphores (rows
        coming, rows leaving)."""
        return [pltpu.VMEM((2, tile * (d // 128), 128), f32),
                pltpu.SemaphoreType.DMA((2,))]

    def __init__(self, tok_ref, hbm, buf, sem, tile):
        self.tok, self.hbm, self.buf, self.sem = tok_ref, hbm, buf, sem
        self.tile, self.C = tile, buf.shape[1] // tile

    def _each(self, t, fn):
        C, slot = self.C, self.buf.at[t % 2]

        def body(i, _):
            for r in (i * _UNROLL + u for u in range(_UNROLL)):
                at = pl.multiple_of(self.tok[t * self.tile + r] * C, C)
                fn(self.hbm.at[pl.ds(at, C), :],
                   slot.at[pl.ds(pl.multiple_of(r * C, C), C), :])
            return 0

        jax.lax.fori_loop(0, self.tile // _UNROLL, body, 0)

    def come(self, t):
        self._each(t, lambda row, at: pltpu.make_async_copy(
            row, at, self.sem.at[0]).start())

    def came(self, t):
        self._each(t, lambda row, at: pltpu.make_async_copy(
            row, at, self.sem.at[0]).wait())

    def leave(self, t):
        self._each(t, lambda row, at: pltpu.make_async_copy(
            at, row, self.sem.at[1]).start())

    def left(self, t):
        self._each(t, lambda row, at: pltpu.make_async_copy(
            at, row, self.sem.at[1]).wait())

    def add(self, t, same, live, values):
        """Tile t's [tile, C * 128] values into its tokens' rows. ``same``:
        tile t - 1 was this expert's too, so `early` has the rows coming."""
        @pl.when(t > 0)
        def _():
            self.left(t - 1)

        @pl.when(jnp.logical_not(same))
        def _():
            self.come(t)

        self.came(t)
        slot = self.buf.at[t % 2]
        for c in range(self.C):
            slot[pl.ds(c, self.tile, stride=self.C), :] += \
                values[:, c * 128:(c + 1) * 128]
        self.leave(t)

        @pl.when(t == live - 1)
        def _():
            self.left(t)

    def early(self, t, same):
        """Before the tile's products: its rows start coming where no DMA
        of the tile before can still hold one of them."""
        pl.when(same)(lambda: self.come(t))


def _fwd_kernel(te_ref, meta_ref, tok_ref, x_ref, w_ref, wg_ref, wu_ref,
                wd_ref, y_in, y_out, y_buf, y_sem, *, tile):
    del y_in                                  # aliased to y_out
    t = pl.program_id(0)
    live = meta_ref[0]

    @pl.when(t < live)
    def _():
        same = (t > 0) & (te_ref[jnp.maximum(t - 1, 0)] == te_ref[t])
        sums = _RowSums(tok_ref, y_out, y_buf, y_sem, tile)
        sums.early(t, same)
        h = _hidden(x_ref[...], wg_ref[0], wu_ref[0])[-1]
        y = jnp.dot(h, wd_ref[0], preferred_element_type=f32)
        sums.add(t, same, live, w_ref[...] * y)


def _bwd_kernel(te_ref, meta_ref, tok_ref, x_ref, dy_ref, w_ref, wg_ref,
                wu_ref, wd_ref, dx_in, dwg_in, dwu_in, dwd_in, drow_ref,
                dx_out, dwg_out, dwu_out, dwd_out, dwg_acc, dwu_acc, dwd_acc,
                sem, dx_buf, dx_sem, *, tile):
    del dx_in                                 # aliased to dx_out
    t = pl.program_id(0)
    live, carried = meta_ref[0], meta_ref[1]
    e = te_ref[t]
    before = te_ref[jnp.maximum(t - 1, 0)]
    after = te_ref[jnp.minimum(t + 1, pl.num_programs(0) - 1)]
    first = (t == 0) | (before != e)
    last = (t == live - 1) | (after != e)
    accs = (dwg_acc, dwu_acc, dwd_acc)

    def leave(expert):
        """The three DMAs that take the resident sums to ``expert``'s rows
        of the results."""
        return [pltpu.make_async_copy(acc, out.at[expert], sem.at[i])
                for i, (acc, out) in enumerate(
                    zip(accs, (dwg_out, dwu_out, dwd_out)))]

    @pl.when(t < live)
    def _():
        sums = _RowSums(tok_ref, dx_out, dx_buf, dx_sem, tile)
        sums.early(t, jnp.logical_not(first))
        x, wg, wu, wd = x_ref[...], wg_ref[0], wu_ref[0], wd_ref[0]
        dy = dy_ref[...].astype(f32)
        a, b, sig, h = _hidden(x, wg, wu)
        y = jnp.dot(h, wd, preferred_element_type=f32)
        drow_ref[...] = jnp.sum(dy * y, axis=1, keepdims=True)
        dyw = (dy * w_ref[...]).astype(x.dtype)
        dh = _nt(dyw, wd)
        da = (dh * b * sig * (1 + a * (1 - sig))).astype(x.dtype)
        db = (dh * (a * sig)).astype(x.dtype)
        sums.add(t, jnp.logical_not(first), live, _nt(da, wg) + _nt(db, wu))

        # the resident sums are first touched here, behind the products
        # above: the DMAs of the run before have had that long to land
        @pl.when(first & (t > 0))
        def _():
            for copy in leave(before):
                copy.wait()

        @pl.when(first & (t == 0) & (carried != 0))
        def _():
            come = [pltpu.make_async_copy(src.at[e], acc, sem.at[i])
                    for i, (src, acc) in enumerate(
                        zip((dwg_in, dwu_in, dwd_in), accs))]
            for copy in come:
                copy.start()
            for copy in come:
                copy.wait()

        @pl.when(first & ((t > 0) | (carried == 0)))
        def _():
            for acc in accs:
                acc[...] = jnp.zeros_like(acc)

        d, I = wg.shape
        for r in range(0, d, _DW_ROWS):
            rows = slice(r, min(r + _DW_ROWS, d))
            dwg_acc[rows, :] += _tn(x_ref[:, rows], da)
            dwu_acc[rows, :] += _tn(x_ref[:, rows], db)
        for r in range(0, I, _DW_ROWS):
            rows = slice(r, min(r + _DW_ROWS, I))
            dwd_acc[rows, :] += _tn(h[:, rows], dyw)

        @pl.when(last)
        def _():
            for copy in leave(e):
                copy.start()

        @pl.when(t == live - 1)
        def _():
            for copy in leave(e):
                copy.wait()


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
        dimension_semantics=("arbitrary",))}


def _specs(d, I, tile, buffers):
    """BlockSpecs by what a block follows: the tile (held at the last live
    one past the tiles in use) or the tile's expert."""
    vm = pltpu.VMEM

    def at(t, meta):
        return jnp.minimum(t, meta[0] - 1)

    def rows(width):
        return pl.BlockSpec((tile, width),
                            lambda t, te, meta, tok: (at(t, meta), 0),
                            memory_space=vm)

    def expert(shape):
        mode = {} if buffers == 2 else {"pipeline_mode": pl.Buffered(buffers)}
        return pl.BlockSpec((1,) + shape,
                            lambda t, te, meta, tok: (te[at(t, meta)], 0, 0),
                            memory_space=vm, **mode)

    return {"x": rows(d), "col": rows(1), "up": expert((d, I)),
            "down": expert((I, d)), "hbm": pl.BlockSpec(memory_space=pl.ANY)}


# jitted so that a step traces and lowers each launch once, however many
# layers of one shape call it (a launch's body is ~50 DMA equations and
# fifteen products: 0.8 s a layer of set-up otherwise)
@functools.partial(jax.jit, static_argnums=(9, 10, 11))
def _fwd_call(te, meta, tok, xt, w, wg, wu, wd, y, tile, buffers, interpret):
    """The float32 token sums ``y`` [(N + 1) * d / 128, 128] with one
    chunk's weighted results added."""
    (rows, d), I = xt.shape, wg.shape[-1]
    spec = _specs(d, I, tile, buffers)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tile=tile),
        name="moe_grouped_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(rows // tile,),
            in_specs=[spec["x"], spec["col"], spec["up"], spec["up"],
                      spec["down"], spec["hbm"]],
            out_specs=spec["hbm"],
            scratch_shapes=_RowSums.scratch(tile, d)),
        out_shape=jax.ShapeDtypeStruct(y.shape, f32),
        # operands count the three prefetched vectors
        input_output_aliases={8: 0},
        interpret=interpret, **_params(interpret))(
            te, meta, tok, xt, w, wg, wu, wd, y)


@functools.partial(jax.jit, static_argnums=(13, 14, 15))
def _bwd_call(te, meta, tok, xt, dyt, w, wg, wu, wd, dx, dwg, dwu, dwd, tile,
              buffers, interpret):
    """(d row_w [chunk * tile, 1], dx, dwg, dwu, dwd): the chunk's
    row-weight gradient, and the float32 token sums ``dx`` and the three
    float32 gradient arrays with the chunk's tiles added."""
    (rows, d), I = xt.shape, wg.shape[-1]
    spec = _specs(d, I, tile, buffers)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tile=tile),
        name="moe_grouped_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(rows // tile,),
            in_specs=[spec["x"], spec["x"], spec["col"], spec["up"],
                      spec["up"], spec["down"]] + [spec["hbm"]] * 4,
            out_specs=[spec["col"]] + [spec["hbm"]] * 4,
            scratch_shapes=[pltpu.VMEM((d, I), f32), pltpu.VMEM((d, I), f32),
                            pltpu.VMEM((I, d), f32),
                            pltpu.SemaphoreType.DMA((3,))]
            + _RowSums.scratch(tile, d)),
        out_shape=[jax.ShapeDtypeStruct((rows, 1), f32)]
        + [jax.ShapeDtypeStruct(a.shape, f32) for a in (dx, dwg, dwu, dwd)],
        # operands count the three prefetched vectors
        input_output_aliases={9: 1, 10: 2, 11: 3, 12: 4},
        interpret=interpret, **_params(interpret))(
            te, meta, tok, xt, dyt, w, wg, wu, wd, dx, dwg, dwu, dwd)


def _chunks(row_w, row_tok, tile_expert, n_tiles, tile, chunk, N):
    """The plan padded to whole chunks (padding rows: token N, weight 0),
    the chunk's length in this buffer, how many chunks hold a tile in use,
    and ``of(c)``: chunk c's (tokens, weights, the tiles' experts, [tiles in
    use, whether its first run continues the chunk before's last])."""
    chunk = min(chunk, row_tok.shape[0] // tile)    # one trip takes them all
    rows = chunk * tile
    pad = round_up(row_tok.shape[0], rows) - row_tok.shape[0]
    row_tok = jnp.pad(row_tok, (0, pad), constant_values=N)
    row_w = jnp.pad(row_w, (0, pad)).astype(f32)
    tile_expert = jnp.pad(tile_expert, (0, pad // tile), mode="edge")

    def of(c):
        tok = jax.lax.dynamic_slice_in_dim(row_tok, c * rows, rows)
        w = jax.lax.dynamic_slice_in_dim(row_w, c * rows, rows)
        te = jax.lax.dynamic_slice_in_dim(tile_expert, c * chunk, chunk)
        carried = (c > 0) & (tile_expert[jnp.maximum(c * chunk - 1, 0)]
                             == te[0])
        meta = jnp.stack([jnp.minimum(n_tiles - c * chunk, chunk),
                          carried]).astype(jnp.int32)
        return tok, w, te, meta

    return row_tok.shape[0], chunk, -(-n_tiles // chunk), of


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def grouped_ffn(x, wg, wu, wd, row_w, row_tok, tile_expert, n_tiles, tile,
                chunk, buffers, interpret=False):
    """``layers.moe.grouped_ffn`` by ``moe_grouped_fwd`` / ``moe_grouped_bwd``
    over chunks of ``chunk`` tiles: the same arguments, the same sum."""
    return _grouped_fwd(x, wg, wu, wd, row_w, row_tok, tile_expert, n_tiles,
                        tile, chunk, buffers, interpret)[0]


def _grouped_fwd(x, wg, wu, wd, row_w, row_tok, tile_expert, n_tiles, tile,
                 chunk, buffers, interpret):
    N, d = x.shape
    _, _, n_chunks, of = _chunks(row_w, row_tok, tile_expert, n_tiles, tile,
                                 chunk, N)

    def body(c, y):
        tok, w, te, meta = of(c)
        xt = jnp.take(x, tok, axis=0, mode="clip")
        return _fwd_call(te, meta, tok, xt, w[:, None], wg, wu, wd, y, tile,
                         buffers, interpret)

    with jax.named_scope("moe_grouped_ffn_fwd"):
        y = jax.lax.fori_loop(0, n_chunks, body, _RowSums.zeros(N, d))
    return _RowSums.summed(y, N, d, x.dtype), (x, wg, wu, wd, row_w, row_tok,
                                       tile_expert, n_tiles)


def _grouped_bwd(tile, chunk, buffers, interpret, res, dy):
    x, wg, wu, wd, row_w, row_tok, tile_expert, n_tiles = res
    N, d = x.shape
    Rp, chunk, n_chunks, of = _chunks(row_w, row_tok, tile_expert, n_tiles,
                                      tile, chunk, N)

    def body(c, carry):
        dx, dwg, dwu, dwd, drow = carry
        tok, w, te, meta = of(c)
        xt = jnp.take(x, tok, axis=0, mode="clip")
        dyt = jnp.take(dy, tok, axis=0, mode="fill", fill_value=0)
        drt, dx, dwg, dwu, dwd = _bwd_call(
            te, meta, tok, xt, dyt, w[:, None], wg, wu, wd, dx, dwg, dwu, dwd,
            tile, buffers, interpret)
        # rows of a tile past the tiles in use were never written
        drow = jax.lax.dynamic_update_slice_in_dim(
            drow, jnp.where(tok < N, drt[:, 0], 0), c * chunk * tile, 0)
        return dx, dwg, dwu, dwd, drow

    init = (_RowSums.zeros(N, d), jnp.zeros(wg.shape, f32),
            jnp.zeros(wu.shape, f32), jnp.zeros(wd.shape, f32),
            jnp.zeros((Rp,), f32))
    with jax.named_scope("moe_grouped_ffn_bwd"):
        dx, dwg, dwu, dwd, drow = jax.lax.fori_loop(0, n_chunks, body, init)
    return (_RowSums.summed(dx, N, d, x.dtype), dwg.astype(wg.dtype),
            dwu.astype(wu.dtype), dwd.astype(wd.dtype),
            drow[:row_w.shape[0]].astype(row_w.dtype), None, None, None)


grouped_ffn.defvjp(_grouped_fwd, _grouped_bwd)
