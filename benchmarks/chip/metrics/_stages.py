"""The serving metrics sink's ``stages`` block (``repro.serving.metrics``),
shared by the stage readers; None where the program keeps none."""


def stages(rec):
    s = rec.sink.get("stages") or {}
    return s if s.get("waves") else None
