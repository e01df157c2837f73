"""Peak device memory of the run (set-up and window) on the fullest chip,
in GB: the buffers the allocator held at its peak (``peak_bytes_in_use``)
plus the region the runtime reserved for the programs' temporaries
(``peak_bytes_reserved``), read after the window."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9
