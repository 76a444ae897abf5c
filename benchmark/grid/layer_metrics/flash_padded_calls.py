"""Flash-attention calls the process traced in the ``padded`` layout (the
head's width zero-padded to whole lane tiles in a copy in HBM, transposes
on either side), from the program's counter
``mx_flash_attention_layout_total{layout}``. 0 says no call was padded:
the kernels took every call's widths where they lie (latent attention's
192-lane keys beside 128-lane values among them), or, off the chip, the
XLA tier took the calls and the kernels none. None where the program has
no such counter or traced no attention call at all
(``mx_attention_mask_total``, which counts a call whatever tier takes
it)."""


def read(ctx):
    try:
        from mxnet_tpu import telemetry
        from mxnet_tpu.telemetry import names
        registry = telemetry.registry()
        calls = registry.counter(names.ATTENTION_MASK,
                                 label_key="kind").values()
        by_layout = registry.counter(names.FLASH_ATTENTION_LAYOUT,
                                     label_key="layout").values()
    except Exception:       # a program without the counters: silent
        return None
    if not calls:
        return None
    return by_layout.get("padded", 0)
