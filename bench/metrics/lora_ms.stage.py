"""lora_ms.stage: device milliseconds per traced stage in the LoRA adapters,
the self time of the stage program's operations under the ``lora.adapter``
scope: the adapters' matmuls, forward and backward, with their gradients
(``layer_data["layers"]`` of the ``stage_scoped`` driver)."""


def read(d):
    layers = d.get("layers") or {}
    scopes, stages = layers.get("scope_s") or {}, layers.get("stages")
    if "lora.adapter" not in scopes or not stages:
        return None
    return 1e3 * scopes["lora.adapter"] / stages
