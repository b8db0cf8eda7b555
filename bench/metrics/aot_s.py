"""Seconds of the translator's ahead-of-time compile
(``translate_breakdown['aot_s']``, summed over the cell's programs)."""


def read(run):
    t = run.records.get("translate") or []
    return sum(b["aot_s"] for b in t) if t else None
