"""mla_ms.stage: device milliseconds per traced stage in multi-head latent
attention, the self time of the stage program's operations under the
``mla.attention`` scope less its adapters' (``lora.adapter``), forward,
rematerialised forward and backward (``layer_data["layers"]`` of the
``stage_scoped`` driver)."""


def read(d):
    layers = d.get("layers") or {}
    scopes, stages = layers.get("scope_s") or {}, layers.get("stages")
    if "mla.attention" not in scopes or not stages:
        return None
    return 1e3 * scopes["mla.attention"] / stages
