"""moe_ms.stage: device milliseconds per traced stage in the mixture of
experts, the self time of the stage program's operations under the scopes
``moe.route`` (the gate), ``moe.experts`` (the held experts) and
``moe.shared`` (the shared experts) together (``layer_data["layers"]`` of
the ``stage_scoped`` driver)."""

MOE_SCOPES = ("moe.route", "moe.experts", "moe.shared")


def read(d):
    layers = d.get("layers") or {}
    scopes, stages = layers.get("scope_s") or {}, layers.get("stages")
    found = [scopes[s] for s in MOE_SCOPES if s in scopes]
    if not found or not stages:
        return None
    return 1e3 * sum(found) / stages
