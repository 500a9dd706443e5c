"""The serving window's engine spans, read in-process from the program's
``repro.tracing`` buffer: the ``serve.dispatch`` spans whose parent is
the newest closed ``serve.loop``.  ``bench/serve.py`` starts the engine's
dispatch thread just before its window and stops it just after; its
warm-up dispatches run on the main thread, outside any loop."""


def window():
    """(loop span, [(dispatch span, its child spans)]), or None where the
    program records no such spans."""
    try:
        from repro import tracing
    except ImportError:
        return None
    every = tracing.spans()
    loops = [s for s in every if s.name == "serve.loop"]
    if not loops:
        return None
    loop = loops[-1]
    kids = {}
    for s in every:
        kids.setdefault(s.parent, []).append(s)
    dispatches = [d for d in kids.get(loop.id, [])
                  if d.name == "serve.dispatch"]
    if not dispatches:
        return None
    return loop, [(d, kids.get(d.id, [])) for d in dispatches]
