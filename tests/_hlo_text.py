"""Reading a compiled module's text (``compiled.as_text()``)."""

import re

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")


def sorts_and_their_guards(hlo_text):
    """Of a compiled module's text: for each ``sort`` instruction, whether
    the computation that holds it is reached only through a
    ``conditional``'s branch."""
    comps, name, entry = {}, None, None
    for line in hlo_text.splitlines():
        m = _HEADER.match(line.strip())
        if m:
            name = m.group(1)
            comps[name] = []
            if line.startswith("ENTRY"):
                entry = name
        elif name is not None:
            comps[name].append(line)

    def guarded(comp):
        seen = set()
        while comp != entry and comp not in seen:
            seen.add(comp)
            ref = re.compile(r"%?" + re.escape(comp) + r"\b(?!\.)")
            caller = next(((c, ln) for c, lines in comps.items()
                           for ln in lines
                           if c != comp and "=" in ln
                           and ref.search(ln.split("=", 1)[1])), None)
            if caller is None:
                return False
            if " conditional(" in caller[1]:
                return True
            comp = caller[0]
        return False

    return [guarded(c) for c, lines in comps.items() for ln in lines
            if " sort(" in ln]
