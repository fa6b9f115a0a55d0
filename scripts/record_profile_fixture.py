"""Record `tests/data/toy_step_v5e.xplane.pb.gz`: ONE `System.run` step of
a toy coupled scene under `profile_dir=`, on the chip, cut to what
`obs.profile.load_device_trace` reads. Re-record after a change to the scope
vocabulary or the run-loop spans (the tests of `tests/test_profile_fold.py`
and `chipbench/tests/test_phases.py` read the recording):

    chiprun -- python scripts/record_profile_fixture.py chiprun_out/fixture
    cp chiprun_out/fixture/toy_step_v5e.xplane.pb.gz tests/data/

The scene: 2 fibers x 8 nodes, a 64-node shell, a 40-node body, tol 1e-7,
`gmres_restart` 10 (the back-substitution runs `restart` trips a cycle:
at the default 100 it alone is 48,000 op events), mixed precision on the
chip. A warm step compiles outside the capture. The cut keeps, of each
device plane, the ``XLA Ops`` / ``XLA Modules`` lines (events without their
stats, an op's HLO text up to its opcode); of the host plane the ``skelly/``
annotations with their ``step``; of the metadata plane each EXECUTED
instruction's name and ``metadata.op_name``. 60,000 op events at 9 bytes
each do not compress: the file is ~560 KiB.
"""

import dataclasses
import gzip
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from skellysim_tpu.obs.profile import (_fields, _instruction, _map_entries,
                                       _utf8)

OPCODE = re.compile(rb" = .*?(?:^|[\s)\]}])([a-z][a-z0-9\-]*)\(")


# ------------------------------------------------------------ the recording

def record(out: str) -> str:
    """Run the scene; returns the path of the dump."""
    import numpy as np

    import jax

    jax.config.update("jax_enable_x64", True)
    from skellysim_tpu import precompute
    from skellysim_tpu.builder import build_simulation
    from skellysim_tpu.config import Body, ConfigSpherical, Fiber
    from skellysim_tpu.io.trajectory import TrajectoryWriter
    from skellysim_tpu.obs import profile as profile_mod
    from skellysim_tpu.system import System
    from skellysim_tpu.utils.bootstrap import enable_compilation_cache

    enable_compilation_cache("auto")
    profile_mod.include_scopes_in_cache_key()    # before the first compile
    cfg = ConfigSpherical()
    dt = 0.05
    cfg.params.eta = 1.0
    cfg.params.dt_initial = dt
    cfg.params.dt_write = dt
    cfg.params.t_final = 10 * dt
    cfg.params.gmres_tol = 1e-7
    cfg.params.adaptive_timestep_flag = False
    cfg.periphery.n_nodes = 64
    cfg.periphery.radius = 6.0
    cfg.bodies = [Body(position=[0.0, 0.0, 0.0], shape="sphere", radius=0.5,
                       n_nodes=40, external_force=[0.0, 0.0, 0.5])]
    cfg.fibers = []
    for k in range(2):
        fib = Fiber(n_nodes=8, length=1.0, bending_rigidity=0.01,
                    radius=0.0125)
        a = np.pi * k
        fib.fill_node_positions(
            np.array([3.0 * np.cos(a), 3.0 * np.sin(a), 0.0]),
            np.array([0.0, 0.0, 1.0]))
        cfg.fibers.append(fib)
    scene = os.path.join(out, "scene")
    os.makedirs(scene, exist_ok=True)
    cfg_path = os.path.join(scene, "skelly_config.toml")
    cfg.save(cfg_path)
    precompute.main([cfg_path])
    system, state, rng = build_simulation(cfg_path)
    system = System(dataclasses.replace(system.params, gmres_restart=10),
                    shell_shape=system.shell_shape, mesh=system.mesh)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, "precision",
          system._precision_for(state), flush=True)
    with TrajectoryWriter(os.path.join(out, "traj.out")) as writer:
        t0 = time.perf_counter()
        state = system.run(state, max_steps=1, writer=writer.write_frame,
                           rng=rng)
        print("warm step s", time.perf_counter() - t0, flush=True)
        system.run(state, max_steps=1, writer=writer.write_frame, rng=rng,
                   profile_dir=os.path.join(out, "prof"),
                   trace_path=os.path.join(out, "trace.jsonl"),
                   metrics_path=os.path.join(out, "metrics.jsonl"))
    (dump,) = profile_mod.find_xplanes(os.path.join(out, "prof"))
    return dump


# ------------------------------------------------------------------ the cut

def varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def vfield(num, value):
    return varint(num << 3) + varint(value)


def bfield(num, payload):
    return varint(num << 3 | 2) + varint(len(payload)) + payload


def trim_hlo(hlo_proto, executed):
    hlo = _fields(hlo_proto)
    mod = _fields(hlo[1][0])
    comps = b""
    for comp_b in mod.get(3, []):
        comp = _fields(comp_b)
        instrs = b""
        for ib in comp.get(2, []):
            ins = _fields(ib)
            if 1 not in ins or _utf8(ins[1][0]) not in executed:
                continue
            body = bfield(1, ins[1][0])
            if 7 in ins:
                meta = _fields(ins[7][0]) or {}
                if 2 in meta:
                    body += bfield(7, bfield(2, meta[2][0]))
            instrs += bfield(2, body)
        if instrs:
            comps += bfield(3, bfield(1, comp.get(1, [b""])[0]) + instrs)
    return bfield(1, bfield(1, mod.get(1, [b""])[0]) + comps)


def trim_plane(plane, executed):
    name = _utf8(plane.get(2, [b""])[0])
    stat_names = {k: _utf8(m.get(2, [b""])[0])
                  for k, m in _map_entries(plane.get(5, []))}
    metas = dict(_map_entries(plane.get(4, [])))
    out = bfield(2, name.encode())
    keep_meta = set()
    if name == "/host:metadata":
        hlo_ids = {k for k, n in stat_names.items() if n == "Hlo Proto"}
        for k in hlo_ids:
            out += bfield(5, vfield(1, k) + bfield(
                2, vfield(1, k) + bfield(2, b"Hlo Proto")))
        for k, m in metas.items():
            stats = b""
            for sb in m.get(5, []):
                st = _fields(sb)
                if st and st.get(1, [None])[0] in hlo_ids and 6 in st:
                    stats += bfield(5, vfield(1, st[1][0])
                                    + bfield(6, trim_hlo(st[6][0], executed)))
            out += bfield(4, vfield(1, k) + bfield(
                2, vfield(1, k) + bfield(2, m.get(2, [b""])[0]) + stats))
        return out
    device = name.startswith("/device:")
    if not device and name != "/host:CPU":
        return None
    step_ids = {k for k, n in stat_names.items() if n == "step"}
    for line_b in plane.get(3, []):
        line = _fields(line_b)
        lname = _utf8(line.get(2, [b""])[0])
        if device and lname not in ("XLA Ops", "XLA Modules"):
            continue
        evs = b""
        for eb in line.get(4, []):
            ev = _fields(eb)
            mname = _utf8(metas.get(ev[1][0], {}).get(2, [b""])[0])
            if not device and not mname.startswith("skelly/"):
                continue
            keep_meta.add(ev[1][0])
            body = vfield(1, ev[1][0]) + vfield(2, ev.get(2, [0])[0]) \
                + vfield(3, ev.get(3, [0])[0])
            for sb in ev.get(4, []):
                st = _fields(sb)
                if st and st.get(1, [None])[0] in step_ids:
                    body += bfield(4, sb)
            evs += bfield(4, body)
        if evs:
            out += bfield(3, bfield(2, lname.encode())
                          + vfield(3, line.get(3, [0])[0]) + evs)
    for k in keep_meta:
        text = metas[k].get(2, [b""])[0]
        if device:      # an op's HLO line, cut after its opcode's "("
            m = OPCODE.search(text)
            text = text[:m.end()] if m else text
        out += bfield(4, vfield(1, k) + bfield(
            2, vfield(1, k) + bfield(2, text)))
    for k in step_ids:
        out += bfield(5, vfield(1, k) + bfield(
            2, vfield(1, k) + bfield(2, b"step")))
    return out


def cut(src: str, dst: str) -> None:
    opener = gzip.open if src.endswith(".gz") else open
    with opener(src, "rb") as fh:
        space = _fields(fh.read())
    executed = set()    # instruction names the device planes' events name
    for plane_b in space.get(1, []):
        plane = _fields(plane_b)
        if _utf8(plane.get(2, [b""])[0]).startswith("/device:"):
            executed |= {_instruction(_utf8(m.get(2, [b""])[0]))[0]
                         for _, m in _map_entries(plane.get(4, []))}
    out = b""
    for plane_b in space.get(1, []):
        trimmed = trim_plane(_fields(plane_b), executed)
        if trimmed is not None:
            out += bfield(1, trimmed)
    with gzip.open(dst, "wb", compresslevel=9) as fh:
        fh.write(out)
    print("cut", src, "->", dst, len(out), "bytes before gzip")


if __name__ == "__main__":
    out_dir = sys.argv[1]
    os.makedirs(out_dir, exist_ok=True)
    cut(record(out_dir),
        os.path.join(out_dir, "toy_step_v5e.xplane.pb.gz"))
