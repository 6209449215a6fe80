"""Row-block parallelism shared by the generators and the references.

A data set is made in blocks of ``BLOCK_ROWS`` rows.  Block ``b`` of a run
with seed ``s`` draws from its own generator, seeded by ``(s, b)``, so the
same seed gives the same bytes whatever the number of threads.  numpy's
generators, ufuncs and BLAS calls release the interpreter lock on large
arrays, so the blocks run on every host core at once.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from threadpoolctl import threadpool_limits

#: Rows per block.  A caller whose temporaries per block would pass
#: glibc's largest mmap threshold (32 MiB) takes smaller blocks, so that they
#: are reused from the heap instead of mapped and unmapped again: on the
#: chip's host, unmapped memory is not handed back at once, and churning it
#: ran a 40 GiB machine out of memory.
BLOCK_ROWS = 1 << 20


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` and the sub-stream ``stream``; any whole
    number is a valid seed, however large."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), *map(int, stream)]))


def map_blocks(n: int, fn, block_rows: int | None = None,
               workers: int | None = None) -> list:
    """``[fn(b, lo, hi) for each block of n rows]``, in block order, run on
    a pool of threads (one per host core unless ``workers`` says
    otherwise) with BLAS held to one thread per call; the first failure is
    re-raised."""
    block_rows = block_rows or BLOCK_ROWS
    blocks = [(b, lo, min(lo + block_rows, n))
              for b, lo in enumerate(range(0, n, block_rows))]
    workers = workers or os.cpu_count() or 1
    with threadpool_limits(1, "blas"), \
            ThreadPoolExecutor(max_workers=workers) as pool:
        futures = [pool.submit(fn, *blk) for blk in blocks]
        return [fut.result() for fut in futures]


def fill(n: int, fill_block, block_rows: int | None = None) -> None:
    """Call ``fill_block(b, lo, hi)`` for every block of ``n`` rows."""
    map_blocks(n, fill_block, block_rows)
