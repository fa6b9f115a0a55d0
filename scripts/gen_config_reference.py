"""Generate docs/config_reference.md from the TOML schema dataclasses.

The reference documents its config surface with Sphinx autodoc over
`skelly_config.py` (`/root/reference/docs/source/config.rst`); this is the
same idea without the Sphinx dependency: introspect `config.schema` and emit
one markdown section per TOML table, every field with type + default.

    python scripts/gen_config_reference.py          # rewrites the doc
    python scripts/gen_config_reference.py --check  # CI: fail if stale
"""

import dataclasses
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from skellysim_tpu.config import schema

DOC_PATH = os.path.join(REPO_ROOT, "docs", "config_reference.md")

# (schema class, TOML table, note) in file order
SECTIONS = [
    (schema.Params, "[params]",
     "Global simulation parameters (reference `Params`, "
     "`include/params.hpp:7-67`). The TPU-specific knobs at the bottom have "
     "no reference analogue; see `skellysim_tpu/params.py` for the runtime "
     "semantics of each. Three of them default to `'auto'` and follow what "
     "the code can observe: `solver_precision` (mixed for a float64 state on "
     "an accelerator, full elsewhere), `refine_pair_impl` (the Pallas "
     "double-float tile on a TPU, the XLA double-float blocks on another "
     "accelerator, native float64 on a CPU) and `kernel_impl` (the fused "
     "Pallas tile on a TPU for pair sums whose operands are not float64, "
     "XLA's `'exact'` tile everywhere else; `'exact'`, `'mxu'`, `'df'`, "
     "`'pallas'` and `'pallas_df'` ask for one tile by name)."),
    (schema.DynamicInstability, "[dynamic_instability]",
     "Microtubule nucleation/catastrophe dynamics "
     "(`params.hpp:21-35`); active when `nucleation_rate > 0`."),
    (schema.PeripheryBinding, "[periphery_binding]",
     "Fiber plus-end binding to the periphery "
     "(`params.hpp:37-44`)."),
    (schema.Fiber, "[[fibers]]",
     "One table per fiber (reference `fiber_finite_difference.hpp`). "
     "`n_nodes` may differ per fiber — mixed resolutions land in separate "
     "compute buckets automatically."),
    (schema.SphericalPeriphery, "[periphery] (spherical)",
     "`shape = 'sphere'` (reference `SphericalPeriphery`)."),
    (schema.EllipsoidalPeriphery, "[periphery] (ellipsoidal)",
     "`shape = 'ellipsoid'`."),
    (schema.RevolutionPeriphery, "[periphery] (surface of revolution)",
     "`shape = 'surface_of_revolution'`; the envelope functions follow "
     "the reference's `shape_gallery.Envelope` contract."),
    (schema.Body, "[[bodies]]",
     "One table per rigid body (reference `body_spherical.cpp` / "
     "`body_ellipsoidal.cpp`)."),
    (schema.Point, "[[point_sources]]",
     "Point force/torque sources with optional time-to-live "
     "(`point_source.hpp`)."),
    (schema.BackgroundSource, "[background]",
     "Background flow (uniform and/or shear); incompatible with a "
     "periphery, like the reference's sanity check (`system.cpp:625-626`)."),
    (schema.RuntimeConfig, "[runtime]",
     "Host-side execution policy shared by every CLI (run, ensemble, "
     "serve, listener): the skelly-bucket capacity ladders that quantize "
     "scene shapes onto warm compiled programs, and the persistent XLA "
     "compilation cache (default-on; see docs/performance.md \"Warm "
     "programs and capacity buckets\"). Never enters the traced program."),
    (schema.ServeConfig, "[serve]",
     "Sizing for `python -m skellysim_tpu.serve` (docs/serving.md). Lives "
     "in the SERVER's run config alongside the usual tables: the config's "
     "fibers/params define the warm compiled-program contract tenants admit "
     "against, `[serve]` sizes the service around it. Ignored by "
     "`skellysim-tpu run`."),
    (schema.EnsembleSweep, "[ensemble] (sweep-spec file)",
     "NOT part of `skelly_config.toml`: the `[ensemble]` table lives in its "
     "own sweep-spec TOML consumed by `python -m skellysim_tpu.ensemble` "
     "(docs/ensemble.md), pointing at a base run config via `base_config`."),
    (schema.SweepAxis, "[[ensemble.sweep]] (sweep-spec file)",
     "One swept parameter per table: a dotted path into the BASE config "
     "(`fibers.0.length`, `background.uniform`) plus its values; members "
     "take the cartesian product over all axes, times `replicas`. Only "
     "state values are sweepable (params exceptions: `params.t_final`, "
     "`params.seed`) — see docs/ensemble.md."),
]


def _default_repr(f: dataclasses.Field):
    if f.default is not dataclasses.MISSING:
        return repr(f.default)
    if f.default_factory is not dataclasses.MISSING:  # type: ignore[misc]
        try:
            return repr(f.default_factory())
        except Exception:
            return f.default_factory.__name__
    return "(required)"


def _type_repr(f: dataclasses.Field) -> str:
    t = f.type
    return t if isinstance(t, str) else getattr(t, "__name__", str(t))


def render() -> str:
    out = [
        "# Configuration reference",
        "",
        "The TOML surface accepted by `skellysim-tpu run` / "
        "`config.schema.load_config`. Field names, defaults, and table "
        "layout match the reference's `skelly_config.py` so existing "
        "configs port unchanged; TPU-only extensions are marked in "
        "[params]. Generated by `scripts/gen_config_reference.py` — edit "
        "the schema docstrings/comments, not this file.",
        "",
    ]
    for cls, table, note in SECTIONS:
        out.append(f"## `{table}`")
        out.append("")
        out.append(note)
        out.append("")
        out.append("| field | type | default |")
        out.append("|---|---|---|")
        own = set()
        for base in cls.__mro__[1:]:
            if dataclasses.is_dataclass(base):
                own |= {f.name for f in dataclasses.fields(base)}
        for f in dataclasses.fields(cls):
            inherited = f.name in own and cls.__mro__[1] is not object
            mark = "" if not inherited else " *(base)*"
            out.append(f"| `{f.name}`{mark} | {_type_repr(f)} "
                       f"| `{_default_repr(f)}` |")
        out.append("")
    return "\n".join(out)


def main():
    text = render()
    if "--check" in sys.argv:
        with open(DOC_PATH) as fh:
            if fh.read() != text:
                print("docs/config_reference.md is stale; "
                      "run scripts/gen_config_reference.py", file=sys.stderr)
                return 1
        print("config reference up to date")
        return 0
    with open(DOC_PATH, "w") as fh:
        fh.write(text)
    print(f"wrote {DOC_PATH}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
