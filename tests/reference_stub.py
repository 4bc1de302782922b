"""Import helper for the torch reference at /root/reference.

Stubs the heavyweight training deps (fairscale, pytorch_lightning, torchmetrics)
the reference's __init__ chains import but its backends don't need, so the
backend modules can serve as conversion ground truth in tests without network or
GPU. Where the reference is absent the importing test module is skipped, with
that reason. Test-infrastructure only."""

import os
import sys
import types

import pytest

REFERENCE_PATH = "/root/reference"


def import_reference():
    if not os.path.isdir(REFERENCE_PATH):
        pytest.skip(f"the torch reference is not at {REFERENCE_PATH}", allow_module_level=True)
    if REFERENCE_PATH not in sys.path:
        sys.path.insert(0, REFERENCE_PATH)

    import importlib.machinery

    created = []

    def stub(name, attrs=()):
        if name in sys.modules:
            return sys.modules[name]
        mod = types.ModuleType(name)
        mod.__spec__ = importlib.machinery.ModuleSpec(name, None)
        for a in attrs:
            setattr(mod, a, type(a, (), {}))
        sys.modules[name] = mod
        created.append(name)
        return mod

    try:
        fs = stub("fairscale")
        fsnn = stub("fairscale.nn")
        fsnn.checkpoint_wrapper = lambda m, offload_to_cpu=False: m
        fs.nn = fsnn
        pl = stub("pytorch_lightning", ["LightningModule", "LightningDataModule", "Trainer", "Callback"])
        stub("pytorch_lightning.loggers", ["TensorBoardLogger"])
        util = stub("pytorch_lightning.utilities", [])
        util.rank_zero_only = lambda f: f
        stub("torchmetrics", ["Accuracy"])
        pl.LightningModule.__init__ = lambda self: None
        tv = stub("torchvision", [])
        tv.transforms = stub("torchvision.transforms", ["Compose", "Normalize", "ToTensor", "RandomCrop", "CenterCrop", "Lambda"])
        stub("cv2", [])
        stub("pretty_midi", ["PrettyMIDI", "Note", "Instrument", "ControlChange"])

        import perceiver  # noqa: F401

        # Eagerly load every reference subtree the tests draw from, while the
        # stubs are still installed (the reference resolves these lazily, so a
        # later `from perceiver.model.x import ...` in a test would otherwise
        # re-trigger stub imports after cleanup below).
        import importlib

        for sub in (
            "perceiver.model.core",
            "perceiver.model.text.classifier",
            "perceiver.model.text.common",
            "perceiver.model.text.mlm",
            "perceiver.model.vision.image_classifier",
            "perceiver.model.vision.optical_flow.backend",
            "perceiver.model.audio.symbolic.backend",
        ):
            importlib.import_module(sub)
    finally:
        # The reference's module tree now holds direct references to every stub it
        # imported; dropping OUR stubs from sys.modules keeps them from shadowing
        # genuine installs for the rest of the process (a bare `stub("cv2")` left
        # in sys.modules made the real-binary tier's importorskip("cv2") find an
        # empty husk instead of real OpenCV, or skip-proof a pretty_midi that was
        # never installed) — also when the import above fails half way. Modules that
        # were already present are left untouched.
        for name in created:
            sys.modules.pop(name, None)

    return perceiver
