"""Grouped products of the sparse experts that a step leaves to
``lax.ragged_dot`` (the XLA tier of ``ops/moe.py``) where the kernels of
``ops/kernels/grouped_dot.py`` decline: the sites the program counted
under ``mx_moe_grouped_dot_total{tier="xla"}`` an expert layer it traced
(``mx_moe_dispatch_total``: the process traces a step more than once),
times the configuration's expert layers. The XLA tier counts the products
it calls, two a layer without a gate (their three backward products are
autodiff's transposes, no call site); 0 is the reading wanted, every
product a kernel's. None where the program has no such counter, traced no
expert layer, or the configuration names no pattern of layers."""


def read(ctx):
    try:
        from mxnet_tpu import telemetry
        from mxnet_tpu.telemetry import names
        registry = telemetry.registry()
        products = registry.counter(names.MOE_GROUPED_DOT,
                                    label_key="tier").values()
        traced = sum(registry.counter(names.MOE_DISPATCH,
                                      label_key="path").values().values())
        cfg = ctx["cfg"]
        layers = cfg["hybrid_override_pattern"][
            :cfg["num_hidden_layers"]].count("E")
    except Exception:       # a program or a configuration without them
        return None
    if not traced or not products:
        return None
    return products.get("xla", 0) * layers / traced
