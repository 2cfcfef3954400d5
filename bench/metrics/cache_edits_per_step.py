"""Eager cache edits per engine step (KV cache edits): the program's
``cache_edit`` spans over its ``engine_step`` spans, both counted where
they start in the window.  A program that records no ``step_inputs``
span does not name its cache edits either, and reads nothing."""
UNIT = "edits/step"


def read(run):
    names = [name for name, _, _, _ in run.window_spans]
    steps = names.count("engine_step")
    if not steps or "step_inputs" not in names:
        return None
    return names.count("cache_edit") / steps
