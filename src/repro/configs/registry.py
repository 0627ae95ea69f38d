"""Architecture registry: ``get_arch("<id>")`` -> ArchSpec.

Every assigned architecture (plus the paper's own JEDI-net models and the
other jet taggers of the trigger path) registers here; the launcher, dry-run
sweep, smoke tests and benchmarks all resolve archs through this module
(``--arch <id>``).
"""

from __future__ import annotations

import importlib

from repro.configs.base import ArchSpec

ARCH_MODULES = {
    # LM family
    "arctic-480b": "repro.configs.arctic_480b",
    "moonshot-v1-16b-a3b": "repro.configs.moonshot_v1_16b_a3b",
    "h2o-danube-1.8b": "repro.configs.h2o_danube_1_8b",
    "minicpm-2b": "repro.configs.minicpm_2b",
    "phi3-medium-14b": "repro.configs.phi3_medium_14b",
    # GNN family
    "gcn-cora": "repro.configs.gcn_cora",
    "pna": "repro.configs.pna",
    "meshgraphnet": "repro.configs.meshgraphnet",
    "equiformer-v2": "repro.configs.equiformer_v2",
    # RecSys
    "fm": "repro.configs.fm",
    # the paper's own models
    "jedinet-30p": "repro.configs.jedi_30p",
    "jedinet-50p": "repro.configs.jedi_50p",
    "jedinet-tracks-128": "repro.configs.jedi_tracks_128",
    # jet taggers served beside them on the trigger path
    "part-jetclass": "repro.configs.part_jetclass",
}

#: Families served on the trigger path (``trigger_serve --arch``); every
#: other family is an LM / GNN / RecSys arch of the dry-run sweep.
TRIGGER_FAMILIES = ("jedinet", "part")


def get_arch(arch_id: str) -> ArchSpec:
    if arch_id not in ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(ARCH_MODULES)}")
    mod = importlib.import_module(ARCH_MODULES[arch_id])
    return mod.ARCH


def archs(*families: str) -> list[str]:
    """Registered arch ids whose family is one of ``families``."""
    return [a for a in ARCH_MODULES if get_arch(a).family in families]


ASSIGNED_ARCHS = [a for a in ARCH_MODULES
                  if get_arch(a).family not in TRIGGER_FAMILIES]
ALL_ARCHS = list(ARCH_MODULES)


def iter_cells(archs=None, include_skipped: bool = False):
    """Yield (arch_spec, shape_spec) for every dry-run cell."""
    for arch_id in (archs or ASSIGNED_ARCHS):
        spec = get_arch(arch_id)
        shapes = spec.shapes if include_skipped else spec.runnable_shapes()
        for shape in shapes.values():
            yield spec, shape
