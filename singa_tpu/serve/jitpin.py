"""Serve-wide jit-cache census: the recompile pin, as a library call.

``bench_serve`` has always pinned "zero runtime recompiles" by counting
jit-cache entries across every executable the serve stack dispatches
(engine prefill/decode, prefix cache, paged arena + its AOT cost-table
cache, and the tp/ep/pp sharded-twin caches) before and after the timed
runs.  The federation round needs that same census ACROSS THE PROCESS
BOUNDARY — a ``DistFleet`` worker reports its own count over the
telemetry op so a 2-process bench can prove the warm path compiled
nothing — so the counter lives here in the library and the benches
import it.

Returns ``None`` (never a guess) when the running jax build does not
expose ``_cache_size`` — callers report "unavailable" instead of a
false pin.
"""


def jit_cache_size():
    """Total jit-cache entries across every serve executable in THIS
    process, or ``None`` if the jax build can't count them."""
    from singa_tpu.serve import engine as E
    from singa_tpu.serve import paged as G
    from singa_tpu.serve import prefix as P
    from singa_tpu.serve import tp as T

    total = 0
    for f in (E._pool_decode_step, E._pool_spec_step, E._prefill_one,
              E._prefill_batch, E._prefill_rows, E._write_slot,
              E._chunk_row,
              E._first_from_hidden, P._read_slot,
              G._paged_decode_kernel, G._paged_spec_kernel,
              G._pool_to_row, G._row_to_pool, G._rows_to_pool):
        try:
            total += f._cache_size()
        except Exception:
            return None  # jax without _cache_size: report honestly
    twins = T._twin_cache_size()
    if twins is None:
        return None
    from singa_tpu.serve import ep as EPM
    from singa_tpu.serve import pp as PPM

    ep_twins = EPM._twin_cache_size()
    pp_twins = PPM._twin_cache_size()
    if ep_twins is None or pp_twins is None:
        return None
    return (total + G._compile_cache_size() + twins + ep_twins
            + pp_twins)
