"""Device milliseconds per harness iteration of the operations the program put
under the modules whose path matches ``modules`` (a regex on the module path
that follows the scope ``model``, lib/phase_table.py), on the first chip; with
``kinds_by_layer`` (a key of the configuration that lists one kind a layer,
``layer_types``) the same by the kind of the layer ``layers/<i>`` the module
sits in, forward and backward apart, on an earlier line."""

import re

from lib import phase_table as pt

LAYER = re.compile(r"(?:^|/)layers/(\d+)(?:/|$)")


def read(ctx, entry, modules, kinds_by_layer=None):
    by_chip = pt.rows_by_chip(ctx, entry)
    if by_chip is None:
        return None
    kinds = ctx.facts["model"][kinds_by_layer] if kinds_by_layer else []
    want, by_kind, total = re.compile(modules), {}, 0.0
    for ev, path, backward, _ in by_chip[min(by_chip)]:
        module = path[path.index("model") + 1] if "model" in path[:-1] else None
        if module is None or not want.search(module):
            continue
        ms = ev[2] / 1e6 / ctx.iterations
        total += ms
        layer = LAYER.search(module)
        label = kinds[int(layer.group(1))] if layer and int(layer.group(1)) < len(kinds) else "other"
        key = label + (".bwd" if backward else ".fwd")
        by_kind[key] = by_kind.get(key, 0.0) + ms
    return {"value": total, "by_module_kind_ms": dict(sorted(by_kind.items()))}
