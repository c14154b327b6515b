"""The ResNet family (He et al. 2016) on the served path.

A family file is what the harness knows of one model family, found by the
configuration's ``"family"`` key as ``families/<family>.py``:

* ``TINY``: the keys that shrink a configuration of the family to a toy
  network off a TPU;
* ``reference``: its plain reference (``smolbench/reference/``), which
  imports nothing of the program: ``init_params(cfg)``, ``logits(params,
  cfg, x)``, ``forward(params, cfg, x, quant=)`` and ``item_flops(cfg,
  size)``;
* ``program(cfg, params, accuracy, forward=None)``: the ``ModelSpec`` and the
  model entry that ``SmolRuntime`` is given.  The entry returns the logits
  with ``harness.pixel_sample`` of its input appended.  ``forward(params,
  cfg, x)`` puts a control in the program network's place.

This is the one benchmark file that imports ``repro.models.resnet``.  The
weights stay compile-time constants of the served programs (the closure
below), as the program takes them today.
"""

from __future__ import annotations

from smolbench.reference import resnet as reference

# off a TPU: the same path with a toy network
TINY = {"stage_sizes": [1, 1], "width": 8, "num_classes": 16}


def program(cfg: dict, params, accuracy: dict, forward=None):
    """``(ModelSpec, model_fn)`` of ``cfg`` with ``params``; ``accuracy`` maps
    each format key to the configuration's assumed accuracy on it."""
    import jax.numpy as jnp
    from repro.core.planner import ModelSpec, standard_chain
    from repro.models.resnet import ResNetConfig, resnet_forward

    from smolbench.harness import pixel_sample

    # the planner derives the crop from the input size alone; the reference
    # crops as the configuration states, so the two must agree
    resize_short = standard_chain(cfg["input_size"])[0].target
    if resize_short != cfg["resize_short"]:
        raise ValueError(f"{cfg['name']}: the program resizes the short side to {resize_short}, "
                         f"the configuration states {cfg['resize_short']}")
    spec = ModelSpec(cfg["name"], cfg["input_size"], cfg["assumed"]["exec_throughput_items_per_s"], accuracy)
    if forward is None:
        net = ResNetConfig(cfg["name"], cfg["block"], tuple(cfg["stage_sizes"]), cfg["num_classes"],
                           cfg["width"])

        def forward(p, _cfg, x):
            return resnet_forward(p, net, x)

    def model_fn(x):
        logits = forward(params, cfg, x)
        return jnp.concatenate([logits, pixel_sample(x).astype(logits.dtype)], axis=1)

    return spec, model_fn
