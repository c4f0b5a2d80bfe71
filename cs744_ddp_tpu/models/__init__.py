"""Model zoo: VGG-11/13/16/19 (reference parity), ResNet-18/34 (stress) and
one chip's share of the SDAR-30B-A3B block-diffusion decoder (sdar.py,
imported only when asked for)."""

from . import resnet, vgg

# User-registered factories (name -> () -> (init_fn, apply_fn)); lets tests
# and downstream users plug models into the CLI without editing here.
_CUSTOM = {}


def register_model(name: str, factory) -> None:
    """Register ``factory() -> (init_fn, apply_fn)`` under ``name``."""
    _CUSTOM[name.lower()] = factory


def get_model(name: str, **share):
    """Return (init_fn, apply_fn) for a model name used by the CLI.

    `share` (decoder models only): fields of ``sdar.Shape`` that say what
    this chip holds (layers, held, vocab) and the sequence (seq_len, block).

    ``vgg11`` matches the reference's only model
    (``/root/reference/src/Part 1/model.py:49-50``); ``resnet18`` is the
    BASELINE.json scaling stress config.
    """
    name = name.lower()
    if share and name not in ("sdar-30b-a3b", "sdar-tiny"):
        raise ValueError(f"model {name!r} has no share to set: {share}")
    if name in _CUSTOM:
        return _CUSTOM[name]()
    if name in ("vgg11", "vgg13", "vgg16", "vgg19"):
        return vgg.make(name.upper())
    if name in ("resnet18", "resnet-18"):
        return resnet.make("ResNet18")
    if name in ("resnet34", "resnet-34"):
        return resnet.make("ResNet34")
    if name in ("sdar-30b-a3b", "sdar-tiny"):
        from . import sdar
        base = sdar.TINY if name == "sdar-tiny" else sdar.Shape()
        return sdar.make(base._replace(**share))
    raise ValueError(f"unknown model {name!r}; expected vgg11/13/16/19, "
                     f"resnet18/34, sdar-30b-a3b, sdar-tiny, or one of "
                     f"{sorted(_CUSTOM) or '(none)'}")
