"""Read the collectives out of a compiled program's text
(``compiled.as_text()``): what ``tests/test_sharding.py`` (virtual CPU
devices) and ``tests/test_tpu_compile.py`` (a described v5e:2x2) assert on."""
import re

_COLLECTIVE = (r"= (?P<type>.*?) (?P<op>all-reduce|all-gather|reduce-scatter"
               r"|all-to-all|collective-permute)(?:-start)?\(")


def collectives(hlo_text):
    """``[(op, [shape, ...]), ...]`` of every collective instruction in a
    compiled program's text; a shape is a tuple of ints (a tuple-typed
    collective lists every member)."""
    out = []
    for m in re.finditer(_COLLECTIVE, hlo_text):
        shapes = [tuple(int(d) for d in dims.split(",") if d)
                  for dims in re.findall(r"\w+\[([\d,]*)\]", m.group("type"))]
        out.append((m.group("op"), shapes))
    return out


def activation_allreduces(hlo_text, batch, seq):
    """all-reduces that carry an activation of the WHOLE batch: leading
    dims ``[batch, seq, ...]`` or attention's ``[batch, heads, seq, ...]``."""
    def whole_batch(shape):
        return (len(shape) >= 3 and shape[0] == batch
                and (shape[1] == seq or shape[2] == seq))
    return [(op, shapes) for op, shapes in collectives(hlo_text)
            if op == "all-reduce" and any(whole_batch(s) for s in shapes)]
