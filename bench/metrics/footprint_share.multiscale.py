"""Request intake (cone scenes): the ``request.footprint`` spans (the
requests' per-pixel cone radii) as a share of the window, %. The radii
depend on the frame size alone and are kept per size, so the window
pays one computation per size and a lookup for every later request:
the share reads that cost whatever the number of requests. ``None``
where the program records no such span."""


def read(run):
    spans = [t1 - t0 for name, t0, t1 in run.spans
             if name == "request.footprint"]
    if not spans or run.window.seconds <= 0:
        return None
    return 100.0 * sum(spans) / run.window.seconds
