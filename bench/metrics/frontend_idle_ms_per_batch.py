"""Device idle time in the traced window whose innermost program span is
a front-end one (``frontend.*``), per window batch."""


def read(rec):
    scopes = rec.get("scopes")
    if not scopes or not scopes["has_program_spans"] or not rec["calls"]:
        return None
    idle = sum(s for name, s in scopes["idle_by_program_span"].items()
               if name.startswith("frontend."))
    return idle * 1e3 / len(rec["calls"])
