"""The comparison that decides `correct`.

What is compared is what the timed window itself produced: for a sample of
the window's steps (every step that wrote a trajectory frame first, the
rest drawn from ``--seed``, ``checked_steps`` of the traffic file in all),

* ``ref_residual`` — the configuration's plain reference
  (``references/<name>.py``, which imports nothing of the program) builds
  the step's linear system from the state BEFORE the step and applies it to
  the answer the program gave (positions and tensions as its trajectory
  frame holds them, read back through `TrajectoryReader`; for a step that
  wrote no frame, as `System.run` returned them). The largest explicit
  residual ||b - A x|| / ||b|| over the sample; its limit is the
  configuration's own ``gmres_tol`` — the guarantee it states.
* ``frame_vs_state`` — the largest difference between a frame read back and
  the state the loop returned at that time; exact, limit 0.
* ``time_gap`` — simulated time advanced against steps x dt, in units of dt.
* ``steps_failed`` — window steps the program itself reports as not
  accepted, unhealthy, or with its own explicit residual above the
  tolerance; limit 0.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def failed_steps(rows: list[dict], tol: float) -> list[dict]:
    return [r for r in rows
            if not r["accepted"] or r["health"] != 0
            or r["loss_of_accuracy"] or not (r["residual_true"] <= tol)]


def read_frames(traj_path: str) -> dict:
    """{time: frame} through the program's own reader (the user's view)."""
    from skellysim_tpu.io.trajectory import TrajectoryReader

    reader = TrajectoryReader(traj_path)
    try:
        out = {}
        for i in range(len(reader)):
            frame = reader.load_frame(i)
            out[float(frame["time"])] = {
                "fibers": [{"x": np.asarray(f["x_"], float).reshape(-1, 3),
                            "tension": np.asarray(f["tension_"],
                                                  float).ravel()}
                           for f in frame["fibers"][1]],
                "bodies": [{"position": np.asarray(b["position_"],
                                                   float).ravel(),
                            "solution": np.asarray(b["solution_vec_"],
                                                   float).ravel()}
                           for sub in frame["bodies"] for b in sub],
                "shell": np.asarray(frame["shell"]["solution_vec_"],
                                    float).ravel()}
        return out
    finally:
        reader.close()


def load_reference(name: str):
    path = os.path.join(HERE, "references", name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def frame_at(frames: dict, t: float, dt: float):
    for ft, frame in frames.items():
        if abs(ft - t) <= 1e-6 * dt:
            return frame
    return None


def sample_steps(n_steps: int, with_frame: list[int], k: int, seed: int):
    """Indices of the window's steps to check: those that wrote a frame
    first (latest first, so the longest-run state is in it), the rest
    drawn from the seed."""
    chosen = list(reversed(with_frame))[:k]
    rest = [i for i in range(n_steps) if i not in chosen]
    rng = np.random.default_rng(seed)
    while len(chosen) < min(k, n_steps):
        chosen.append(rest.pop(int(rng.integers(len(rest)))))
    return sorted(chosen)


def frame_state_gap(frame: dict, snap: dict) -> float:
    """Largest absolute difference between a frame and the held state."""
    gap = 0.0
    fx = np.stack([f["x"] for f in frame["fibers"]]) if frame["fibers"] \
        else np.zeros((0, 0, 3))
    ft = np.stack([f["tension"] for f in frame["fibers"]]) \
        if frame["fibers"] else np.zeros((0, 0))
    sx = np.concatenate([g["x"] for g in snap.get("fibers", [])]) \
        if snap.get("fibers") else np.zeros((0, 0, 3))
    st = np.concatenate([g["tension"] for g in snap.get("fibers", [])]) \
        if snap.get("fibers") else np.zeros((0, 0))
    if fx.shape != sx.shape:
        return float("inf")
    if fx.size:
        gap = max(gap, float(np.abs(fx - sx).max()),
                  float(np.abs(ft - st).max()))
    if "shell_density" in snap:
        if frame["shell"].shape != snap["shell_density"].shape:
            return float("inf")
        gap = max(gap, float(np.abs(frame["shell"]
                                    - snap["shell_density"]).max()))
    if "bodies" in snap:
        for i, b in enumerate(frame["bodies"]):
            gap = max(gap,
                      float(np.abs(b["position"]
                                   - snap["bodies"]["position"][i]).max()),
                      float(np.abs(b["solution"]
                                   - snap["bodies"]["solution"][i]).max()))
    return gap


def answer_from_frame(frame: dict, snap: dict) -> dict:
    """The held state with the frame's numbers in their place: what the
    reference is given as the program's answer."""
    out = dict(snap)
    if frame["fibers"]:
        x = np.stack([f["x"] for f in frame["fibers"]])
        t = np.stack([f["tension"] for f in frame["fibers"]])
        groups, off = [], 0
        for g in snap["fibers"]:
            n = g["x"].shape[0]
            groups.append(dict(g, x=x[off:off + n], tension=t[off:off + n]))
            off += n
        out["fibers"] = groups
    if "shell_density" in snap:
        out["shell_density"] = frame["shell"]
    if "bodies" in snap:
        out["bodies"] = dict(
            snap["bodies"],
            position=np.stack([b["position"] for b in frame["bodies"]]),
            solution=np.stack([b["solution"] for b in frame["bodies"]]))
    return out


def check_window(cfg: dict, traffic: dict, rows: list[dict],
                 snaps: list[dict], frames: dict, *, seed: int, tol: float,
                 eta: float, log=print, flow=None,
                 pre_snaps: list[dict] | None = None) -> list[dict]:
    """``snaps[i]`` is the state before window step i, ``snaps[i + 1]``
    after it. Returns [{name, value, limit, ok}, ...]. ``pre_snaps``
    (the controls only) gives the states before each step apart from the
    answers, so that a fault planted in the answers is judged one step at
    a time."""
    n = len(snaps) - 1
    dt = snaps[0]["dt"]
    with_frame = [i for i in range(n)
                  if frame_at(frames, snaps[i + 1]["time"], dt) is not None]
    k = int(traffic.get("checked_steps", 3))
    chosen = sample_steps(n, with_frame, k, seed)
    ref = load_reference(cfg["reference"])
    worst, worst_gap = {"ref_residual": 0.0}, 0.0
    for i in chosen:
        pre, post = (pre_snaps or snaps)[i], snaps[i + 1]
        frame = frame_at(frames, post["time"], dt)
        if frame is not None:
            worst_gap = max(worst_gap, frame_state_gap(frame, post))
            post = answer_from_frame(frame, post)
        if "geometry" in snaps[0]:
            pre = dict(pre, geometry=snaps[0]["geometry"])
        res = ref.step_residual(cfg, pre, post, dt=pre["dt"], eta=eta,
                                flow=flow)
        if not isinstance(res, dict):
            res = {"ref_residual": res}
        log(f"step {i}: reference " + " ".join(
            f"{k}={v:.3e}" for k, v in res.items())
            + f" ({'frame' if frame is not None else 'returned state'}; "
            f"the program's own residual_true={rows[i]['residual_true']:.3e})")
        for name, v in res.items():
            worst[name] = max(worst.get(name, 0.0), v)
    if not chosen:
        worst["ref_residual"] = float("inf")   # nothing compared: not correct
    # the limits are the configuration's own (`limits` in its file, set from
    # readings on the chip: PERF.md); where it gives none for the whole
    # residual, that is held to the tolerance the configuration states
    limits = dict({"ref_residual": tol}, **cfg.get("limits", {}))
    time_gap = abs((snaps[-1]["time"] - snaps[0]["time"])
                   - sum(r["dt"] for r in rows if r["accepted"])) / dt
    n_failed = len(failed_steps(rows, tol))
    # a number the configuration gives no limit for can not pass
    out = [{"name": k, "value": v, "limit": float(limits.get(k, "nan"))}
           for k, v in worst.items()]
    out += [
        {"name": "frame_vs_state", "value": worst_gap, "limit": 0.0},
        {"name": "time_gap", "value": time_gap, "limit": 1e-6},
        {"name": "steps_failed", "value": float(n_failed), "limit": 0.0},
        {"name": "steps_checked", "value": float(len(chosen)),
         "limit": float(min(k, max(n, 1))), "at_least": True},
    ]
    for c in out:
        if c.pop("at_least", False):
            c["ok"] = bool(c["value"] >= c["limit"])
        else:
            c["ok"] = bool(np.isfinite(c["value"])
                           and c["value"] <= c["limit"])
    return out
