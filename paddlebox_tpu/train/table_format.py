"""The pass table's layout across dispatches (one chip).

``jax.jit`` pins a program's arguments and results to the device's DEFAULT
layout. For a narrow array that is not the layout the compiled loop wants: a
TPU lays ``f32[18.9M, 69]`` out column-major by default (69 columns padded to
72), while the gather and scatter of the step want a row contiguous (69 lanes
padded to 128), so the superstep copied the whole table on its way in and
again on its way out, every dispatch, and held both copies at its peak.

Here the boundary follows the loop instead. A program built by
``TableFormatProgram`` takes and returns the state's table leaf in ONE format:
the format the table is in, or, while the table is not up yet (its leaf still
a ``ShapeDtypeStruct``), whatever the compiler picks for it (``Layout.AUTO``).
``put_table`` then brings the table up in that format, and every later program
of the pass is compiled for the format the table has. The compiler already
made this choice inside the loop; nothing here looks at a shape.

Where the backend reports no layouts (``arr.format.layout is None``) every
path is plain ``jax.jit`` and a default upload.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from paddlebox_tpu.train.train_step import TrainState
from paddlebox_tpu.utils import compilecache
from paddlebox_tpu.utils.monitor import STAT_ADD

# a host table goes up in pieces of this many bytes: beside the table in its
# format the device holds one piece, never a second whole table
UPLOAD_CHUNK_BYTES = 256 << 20


def aval_of(x) -> jax.ShapeDtypeStruct:
    """Shape, dtype and (where the array is committed to one) sharding: what
    jit keys its executable on. A leaf that is an aval already stays one."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if x.committed else None
    )


def format_of(x: jax.Array) -> Optional[Format]:
    """An array's format, None where the backend reports no layout."""
    fmt = x.format
    return None if fmt.layout is None else fmt


def loop_format(sharding) -> Format:
    """The format asked for a table that is not up yet: the compiler's own
    choice for the program that takes it."""
    return Format(Layout.AUTO, sharding)


def _state_prefix(fmt: Format) -> TrainState:
    # None: as jit places and lays out a leaf unasked
    return TrainState(table=fmt, params=None, opt_state=None, auc=None, step=None)


def jit_state_step(fun: Callable, fmt: Optional[Format] = None, fmt_out: Optional[Format] = None):
    """``jax.jit`` of ``fun(state, feed) -> (state, out)`` with the state
    donated and its table leaf in ``fmt`` on the way in (None: as it comes)
    and ``fmt_out`` on the way out (None: the default layout)."""
    return jax.jit(
        fun,
        donate_argnums=(0,),
        in_shardings=None if fmt is None else (_state_prefix(fmt), None),
        out_shardings=None if fmt_out is None else (_state_prefix(fmt_out), None),
    )


class TableFormatProgram:
    """``fun(state, feed) -> (state, out)`` on one chip, the state donated,
    compiled ahead of its first call, once per feed shape and table format,
    with the table leaf in one format in and out (so the donated buffer is
    the result's). ``state.table`` may still be an aval: then the compiler
    chooses the format, and ``executable`` says which beside the program."""

    def __init__(self, fun: Callable):
        self._fun = fun
        self._built: Dict[tuple, tuple] = {}

    def executable(self, state: TrainState, feed) -> tuple:
        """(the compiled program for these arguments, the format it takes
        and hands back the table in: None where the backend reports none)."""
        table = state.table
        if isinstance(table, jax.Array):
            fmt = format_of(table)
        else:  # not up yet; another leaf says whether the backend has layouts
            fmt = loop_format(table.sharding) if format_of(state.step) else None
        key = (feed.shape, str(feed.dtype), fmt)
        built = self._built.get(key)
        if built is None:
            built = self._built[key] = self._build(state, feed, fmt)
            # a choice left to the compiler, under its own name too
            self._built[key[:2] + (built[1],)] = built
        return built

    def _build(self, state, feed, fmt: Optional[Format]) -> tuple:
        avals = jax.tree.map(aval_of, (state, feed))
        if fmt is None:
            return jit_state_step(self._fun).lower(*avals).compile(), None
        # a layout does not survive jax's persistent cache (utils/compilecache)
        with compilecache.bypassed():
            exe = jit_state_step(self._fun, fmt, fmt).lower(*avals).compile()
            fin, fout = exe.input_formats[0][0].table, exe.output_formats[0].table
            if fin != fout:  # the alias would be dropped: the result as the entry got it
                exe = jit_state_step(self._fun, fin, fin).lower(*avals).compile()
        if fin.layout != _default_layout(avals[0].table):
            compilecache.suspend("the pass table lies in the layout its loop carries it in")
        return exe, fin

    def __call__(self, state: TrainState, feed):
        return self.executable(state, feed)[0](state, feed)

    def _cache_size(self) -> int:
        """Executables built, as a jitted function counts its own."""
        return len({id(exe) for exe, _ in self._built.values()})


def _default_layout(aval: jax.ShapeDtypeStruct) -> Layout:
    """The layout a plain ``jit`` gives an array of this shape on its device."""
    born = jax.jit(jnp.zeros, static_argnums=(0, 1), out_shardings=aval.sharding)
    return born.lower(aval.shape, aval.dtype).compile().output_formats.layout


def _write_rows(table, rows, at):
    return jax.lax.dynamic_update_slice(table, rows, (at, 0))


def _flat(table):
    return table.reshape(-1, table.shape[-1])


def put_table(src, device, fmt: Optional[Format] = None) -> jax.Array:
    """The pass table ``src`` (host or device, ``[..., W]``) as the committed
    ``[rows, W]`` device array of a pass state, in ``fmt`` (None: the default
    layout, by a plain upload).

    A host table goes up straight into ``fmt``, piece by piece into a buffer
    born in it (``device_put(host, fmt)`` would upload in the default layout
    and copy on the device: two whole tables at once). A table born on the
    device is brought to ``fmt`` by one device copy, counted in the stat
    ``state.table_relayouts``."""
    if fmt is None:
        return jax.device_put(jnp.asarray(_flat(src)), device)
    if isinstance(src, jax.Array):
        if src.ndim == 2 and src.committed and format_of(src) == fmt:
            return src
        STAT_ADD("state.table_relayouts", 1)
        return jax.jit(_flat, out_shardings=fmt)(src)
    flat = _flat(src)
    n, width = flat.shape
    rows = min(n, max(1, UPLOAD_CHUNK_BYTES // (width * flat.dtype.itemsize)))
    table = jax.jit(jnp.zeros, static_argnums=(0, 1), out_shardings=fmt)(
        flat.shape, flat.dtype
    )
    write = jax.jit(_write_rows, donate_argnums=(0,), out_shardings=fmt)
    for at in range(0, n, rows):
        at = min(at, n - rows)  # the last piece overlaps the one before: one shape
        table = write(table, flat[at : at + rows], np.int32(at))
        # one piece on the device at a time: uploads queued ahead of their
        # writes would hold the whole table a second time after all
        table.block_until_ready()
    return table
