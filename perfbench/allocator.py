"""The allocator setting a configuration states for its deployment.

A configuration's file may give ``"launch": {"glibc_malloc": {"mmap_threshold":
bytes, "trim_threshold": bytes}}``: the job's launch environment, as
``MALLOC_MMAP_THRESHOLD_=... MALLOC_TRIM_THRESHOLD_=...`` would set it. The
harness applies it through ``mallopt`` before numpy allocates anything, prints
it in ``setup_parts``, and refuses to run (no result line) where it cannot be
applied: a run under another allocator mode is another deployment.

Why ``criteo_lr`` states one (my chip runs, PR 23): the chip's host is a small
virtual machine on which a page touched for the first time is dear, and glibc
hands large blocks back to the kernel when they are freed. The sparse fit
builds its layout from a few hundred numpy temporaries of 5 to 70 MB per fit,
so the same code ran at three speeds there: 3.48 s a fit in most processes,
4.18 s in about one of three - the whole difference inside
``OneHotSparseLayout.build`` - and 5.1 s with every large block mapped afresh.
With both thresholds at 1 GiB (freed memory stays in the heap and is used
again) every process ran 3.02 s a fit. The defect is the program's (PERF.md,
"For the program"); a configuration without ``launch`` runs under the default
allocator and shows it.
"""
from __future__ import annotations

import ctypes

M_TRIM_THRESHOLD = -1
M_MMAP_THRESHOLD = -3


def apply(setting: dict | None) -> str:
    """Apply a configuration's ``launch.glibc_malloc``; returns what was set,
    for ``setup_parts``. Raises ``RuntimeError`` where it cannot be set."""
    if not setting:
        return "default"
    try:
        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError) as e:
        raise RuntimeError(f"launch.glibc_malloc needs glibc's mallopt: {e}") from e
    for option, key in ((M_MMAP_THRESHOLD, "mmap_threshold"), (M_TRIM_THRESHOLD, "trim_threshold")):
        if mallopt(option, int(setting[key])) != 1:
            raise RuntimeError(f"mallopt refused {key}={setting[key]}")
    return f"mmap_threshold={setting['mmap_threshold']} trim_threshold={setting['trim_threshold']}"
