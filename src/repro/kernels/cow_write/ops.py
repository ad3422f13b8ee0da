"""Public entry point for the fused COW write.

On TPU this dispatches to the Pallas kernel; elsewhere (CPU hosts) a
``use_kernel=True`` request runs the kernel body in interpret mode, and
the default falls back to the jnp oracle.  Both paths are bit-exact on
every non-dump row (the dump row's content is unspecified — see
``repro.core.pool``).
"""

from __future__ import annotations

import jax

from repro.kernels.cow_write.kernel import cow_write_delta_pallas, cow_write_pallas
from repro.kernels.cow_write.ref import cow_write_delta_ref, cow_write_ref
from repro.kernels.dispatch import resolve_kernel_mode
from repro.kernels.pool_rows import as_rank3


def cow_write(
    data: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    pos: jax.Array,
    values: jax.Array,
    *,
    keep: jax.Array | None = None,
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused copy-on-write + item write.

    data: [num_blocks + 1, *block_shape] (trailing dump row);
    src/dst/pos: [n] int32 (dump-routed rows are skipped);
    values: [n, *item_shape].  Returns the updated data array.

    ``keep`` (``[n, block_size]`` bool, optional) selects the sub-block
    delta path: only kept slots are copied from the source block, the
    rest of the emitted block is zero-filled, and the written item still
    lands at ``pos``.  ``keep=None`` is the whole-block path, byte-for-
    byte the pre-delta kernel invocation.
    """
    use_kernel, interpret = resolve_kernel_mode(use_kernel, interpret)
    if not use_kernel:
        if keep is None:
            out = cow_write_ref(data, src, dst, pos, values)
        else:
            out = cow_write_delta_ref(data, src, dst, pos, values, keep)
    else:
        rows = as_rank3(data)
        vals = values.astype(data.dtype)
        if keep is None:
            out = cow_write_pallas(rows, src, dst, pos, vals, interpret=interpret)
        else:
            out = cow_write_delta_pallas(
                rows, src, dst, pos, vals, keep, interpret=interpret
            )
        out = out.reshape(data.shape)
    # Skipped rows self-copied the dump row in whatever order the backend
    # chose; re-zero it so pools compare leaf-for-leaf across paths.
    return out.at[out.shape[0] - 1].set(0)
