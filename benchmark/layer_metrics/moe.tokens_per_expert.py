"""Mean (token, choice) pairs ONE held expert of ONE expert layer was
given in a decode step of the window: the program's counter
`serve_moe_routed_tokens_total{expert}` summed over the held experts
(window delta), over decode dispatches x held experts x expert layers.
In the deployment the chip's experts see 16 x the slots' tokens."""


def read(run):
    steps = run.counts.get("decode_dispatches")
    given = sum(v for k, v in run.counts.items()
                if k.startswith("window.serve_moe_routed_tokens_total"))
    if not steps or not given:
        return None
    sz = run.model.sizes(run.config, run.rehearse)
    layers = sum(t == "sparse" for t in sz["mlp_layer_types"])
    return given / (steps * sz["experts_held"][1] * layers)
