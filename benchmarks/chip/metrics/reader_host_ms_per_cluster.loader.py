"""Host milliseconds per cluster on the reader's device path over the
window: ``ReaderStats`` pread + decompress + host-to-device upload time,
over the clusters staged for the device."""


def read(ctx):
    w = ctx.window
    n = w.get("device_clusters")
    if not n:
        return None
    ns = w["reader_io_ns"] + w["reader_decompress_ns"] + w["reader_h2d_ns"]
    return ns / 1e6 / n
