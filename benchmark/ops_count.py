"""Operations and bytes the algorithm needs per learner step, from the
configuration's shapes alone.

A lower bound on work, on purpose: recomputation, zero-cotangent rows,
activations spilled to HBM and gradients written out and read back do not
count, so a share of a peak computed from these cannot pass 100% unless the
time it is divided by leaves work out.

One learner step on a batch of B transitions needs
  * three forwards per transition: online net on ``obs`` and ``next_obs``
    (double-Q action selection), target net on ``next_obs``;
  * one backward through the online forward on ``obs``: input and weight
    gradients of every layer, except the first layer's input gradient
    (pixels need none);
  * the optimizer's pass over parameters and second moment;
  * the gather of 2*B observations from the ring, the transition scalars,
    and the restamp of B priorities; with ``sample_ahead`` one pass over the
    ring's mass vector per call, without it one per step.
"""

from __future__ import annotations

_KERNELS = ((8, 4), (4, 2), (3, 1))  # (size, stride) of the conv torso, VALID
_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def conv_output_sizes(h: int) -> list:
    out = []
    for k, s in _KERNELS:
        h = (h - k) // s + 1
        out.append(h)
    return out


def layer_table(cfg: dict) -> list:
    """[(name, forward FLOPs per sample, parameters, needs input gradient)]."""
    h, w, cin = cfg["obs_shape"]
    if h != w:
        raise ValueError(f"square observations only, got {cfg['obs_shape']}")
    rows = []
    for i, ((k, _), ch, out) in enumerate(
        zip(_KERNELS, cfg["channels"], conv_output_sizes(h))
    ):
        macs = out * out * ch * k * k * cin
        rows.append((f"conv{i + 1}", 2 * macs, k * k * cin * ch + ch, i > 0))
        cin = ch
    flat = out * out * cin
    hid, a = cfg["hidden"], cfg["num_actions"]
    rows.append(("value_hidden", 2 * flat * hid, flat * hid + hid, True))
    rows.append(("advantage_hidden", 2 * flat * hid, flat * hid + hid, True))
    rows.append(("value_head", 2 * hid, hid + 1, True))
    rows.append(("advantage_head", 2 * hid * a, hid * a + a, True))
    return rows


def param_count(cfg: dict) -> int:
    return sum(p for _, _, p, _ in layer_table(cfg))


def forward_flops(cfg: dict) -> int:
    """Convolution and matmul FLOPs of one forward of one observation."""
    return sum(f for _, f, _, _ in layer_table(cfg))


def backward_flops(cfg: dict) -> int:
    """Weight gradient for every layer, input gradient for all but the
    first: each costs the layer's forward."""
    return sum(f * (2 if dx else 1) for _, f, _, dx in layer_table(cfg))


def flops_per_sample(cfg: dict) -> int:
    return 3 * forward_flops(cfg) + backward_flops(cfg)


def step_flops(cfg: dict) -> int:
    """Per learner step over the global batch."""
    return cfg["batch_size"] * flops_per_sample(cfg)


def step_bytes(cfg: dict) -> int:
    """HBM bytes one chip must move per learner step.  Under data
    parallelism every chip reads and writes the whole replicated parameter
    set and gathers its own B/n rows."""
    p = param_count(cfg)
    prec = cfg["precision"]
    pb = _DTYPE_BYTES[prec["params"]]
    tb = _DTYPE_BYTES[prec["target_params"]]
    mb = _DTYPE_BYTES[prec["second_moment"]]
    n = int(cfg.get("data_parallel", 1))
    b_local = cfg["batch_size"] // n
    h, w, c = cfg["obs_shape"]
    params = p * (2 * pb + tb + 2 * mb)  # read online+target+moment, write online+moment
    rows = b_local * (2 * h * w * c + 5 * 4)  # obs, next_obs; action, reward, discount, index, restamp
    mass = (cfg["replay_capacity"] // n) * 4
    if cfg["sample_ahead"]:
        mass //= cfg["steps_per_call"]
    return params + rows + mass


def step_floor_s(cfg: dict, peaks: dict) -> tuple:
    """(least seconds a step can take on one chip, which bound sets it)."""
    n = int(cfg.get("data_parallel", 1))
    t_flops = step_flops(cfg) / n / peaks["flops_per_s_bf16"]
    t_bytes = step_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")
