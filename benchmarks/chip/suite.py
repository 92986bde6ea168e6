"""A configuration file made into inputs: the app suite, the design space
and the cost model, for the program and for the reference alike.

The suite is built with the program's own suite grammar (the zoo-smoke
profiles checked in with it, generated apps from the seed); the reference
reads only the profiles' raw fields.  The design space and cost model are
built from the numbers in the configuration, so the program and the
reference are held to the same definition.
"""

from __future__ import annotations


def profiles(config: dict, seed: int) -> list:
    """The app suite of ``config``; generated parts take the run's seed."""
    from repro.core.model_zoo import resolve_suite

    out = []
    for part in config["suite"]:
        if "zoo" in part:
            out += list(resolve_suite(part["zoo"], extract_missing=False))
        else:
            out += list(resolve_suite(
                f"gen:{int(part['gen'])}:seed={seed}:mode={part['mode']}"))
    return out


def nominal_model(config: dict):
    return machine_model(config["space"]["nominal"], "nominal")


def machine_model(rates: dict, name: str):
    from repro.core.machine import MachineModel

    return MachineModel(name=name, peak_flops=float(rates["peak_flops"]),
                        hbm_bw=float(rates["hbm_bw"]),
                        ici_bw=float(rates["ici_bw"]),
                        ici_links=int(rates["ici_links"]),
                        inter_pod_bw=float(rates["inter_pod_bw"]))


def param_space(config: dict):
    """The program's design space: ``ParamSpace.default`` around the
    configuration's nominal chip."""
    from repro.core.sweep import ParamSpace

    space = config["space"]
    return ParamSpace.default(nominal=nominal_model(config),
                              span=float(space["span"]),
                              max_links=int(space["max_links"]))


def cost_model(config: dict):
    from repro.core.costmodel import CostModel

    c = config["cost_model"]
    return CostModel(reference=nominal_model(config),
                     area_weights=dict(c["area_weights"]),
                     power_weights=dict(c["power_weights"]),
                     power_exponents=dict(c["power_exponents"]),
                     static_power=float(c["static_power"]))


def reference_cost(config: dict) -> dict:
    """The cost model as the reference reads it."""
    c = dict(config["cost_model"])
    c["reference"] = dict(config["space"]["nominal"])
    return c
