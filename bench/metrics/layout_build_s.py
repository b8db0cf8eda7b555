"""Seconds the translator spent building graph layouts
(``translate_breakdown['preprocess_s']``, summed over the cell's
programs)."""


def read(run):
    t = run.records.get("translate") or []
    return sum(b["preprocess_s"] for b in t) if t else None
