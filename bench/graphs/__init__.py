"""Network assembly per configuration family (``<family>.py``), each with
its plain f32 reference; ``layers.py`` is the shared per-layer helper."""
