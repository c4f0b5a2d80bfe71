"""Model zoo: VGG-11/13/16/19 (reference parity), ResNet-18/34 (stress) and
the decoders, each one chip's share of a published model at its published
widths (`DECODERS`: the module is imported only when asked for)."""

from . import resnet, vgg

# name -> (module, is it the module's CPU test size).  A decoder module
# brings `Shape` (widths as published + this chip's share as its defaults),
# `TINY` and `make(shape)`; a further decoder is a further row.
DECODERS = {
    "sdar-30b-a3b": ("sdar", False),            # block diffusion, MoE
    "sdar-tiny": ("sdar", True),
    "qwen3-next-80b-a3b": ("qwen3next", False),     # linear + full attention
    "qwen3-next-tiny": ("qwen3next", True),
    "xing4.0-29b-a4b": ("xing4", False),    # latent attention, 4 streams
    "xing4-tiny": ("xing4", True),
    "mellum2-12b-a2.5b": ("mellum2", False),    # window + full attention
    "mellum2-tiny": ("mellum2", True),
    "olmo-hybrid-7b": ("olmo_hybrid", False),   # dense, negative eigenvalues
    "olmo-hybrid-tiny": ("olmo_hybrid", True),
}

# User-registered factories (name -> () -> (init_fn, apply_fn)); lets tests
# and downstream users plug models into the CLI without editing here.
_CUSTOM = {}


def register_model(name: str, factory) -> None:
    """Register ``factory() -> (init_fn, apply_fn)`` under ``name``."""
    _CUSTOM[name.lower()] = factory


def get_model(name: str, **share):
    """Return (init_fn, apply_fn) for a model name used by the CLI.

    `share` (decoder models only): fields of the decoder's ``Shape`` that
    say what this chip holds (layers, held, vocab) and the sequence
    (seq_len; a block-diffusion decoder's block).

    ``vgg11`` matches the reference's only model
    (``/root/reference/src/Part 1/model.py:49-50``); ``resnet18`` is the
    BASELINE.json scaling stress config.
    """
    name = name.lower()
    if share and name not in DECODERS:
        raise ValueError(f"model {name!r} has no share to set: {share}")
    if name in _CUSTOM:
        return _CUSTOM[name]()
    if name in ("vgg11", "vgg13", "vgg16", "vgg19"):
        return vgg.make(name.upper())
    if name in ("resnet18", "resnet-18"):
        return resnet.make("ResNet18")
    if name in ("resnet34", "resnet-34"):
        return resnet.make("ResNet34")
    if name in DECODERS:
        import importlib
        modname, tiny = DECODERS[name]
        mod = importlib.import_module("." + modname, __name__)
        base = mod.TINY if tiny else mod.Shape()
        unknown = set(share) - set(base._fields)
        if unknown:
            raise ValueError(f"model {name!r} has no {sorted(unknown)} "
                             f"to set; its share: {base._fields}")
        return mod.make(base._replace(**share))
    raise ValueError(f"unknown model {name!r}; expected vgg11/13/16/19, "
                     f"resnet18/34, {', '.join(DECODERS)}, or one of "
                     f"{sorted(_CUSTOM) or '(none)'}")
