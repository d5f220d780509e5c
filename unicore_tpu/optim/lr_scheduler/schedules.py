"""Pure ``step -> lr`` schedule functions.

TPU-first redesign of the reference's stateful scheduler classes
(``unicore/optim/lr_scheduler/*``): each schedule here is a closed-form
function of the update count, with no object state.  The same function
works in BOTH worlds:

- host-side with python ints/floats — zero device traffic per step (the
  trainer calls it every dispatch);
- inside ``jit`` with traced scalars — so a training setup can fold the
  LR computation into the compiled step entirely (branchless: control
  flow is expressed with ``where``).

The registry classes in this package are thin shims binding CLI args to
these functions; epoch-reactive behavior (per-epoch LR lists,
``--force-anneal``, plateau tracking) stays in the shims because it is
genuinely stateful host logic.
"""

import math


def _traced(*xs):
    import jax.core

    return any(isinstance(x, jax.core.Tracer) for x in xs)


def _where(cond, a, b):
    if _traced(cond, a, b):
        import jax.numpy as jnp

        return jnp.where(cond, a, b)
    return a if cond else b


def _floor(x):
    if _traced(x):
        import jax.numpy as jnp

        return jnp.floor(x)
    return math.floor(x)


def _cos(x):
    if _traced(x):
        import jax.numpy as jnp

        return jnp.cos(x)
    return math.cos(x)


def _log(x):
    if _traced(x):
        import jax.numpy as jnp

        return jnp.log(x)
    return math.log(x)


def polynomial_decay(step, *, base_lr, end_lr, power, warmup_updates,
                     total_updates):
    """Linear warmup to ``base_lr`` then polynomial decay to ``end_lr`` at
    ``total_updates`` (behavioral parity:
    ``unicore/optim/lr_scheduler/polynomial_decay_schedule.py``)."""
    warm = (step / float(warmup_updates)) * base_lr if warmup_updates > 0 else base_lr
    denom = max(total_updates - warmup_updates, 1)
    pct_remaining = 1.0 - (step - warmup_updates) / denom
    decayed = (base_lr - end_lr) * pct_remaining ** power + end_lr
    out = _where(step >= total_updates, end_lr, decayed)
    if warmup_updates > 0:
        out = _where(step <= warmup_updates, warm, out)
    return out


def exponential_decay(step, *, base_lr, decay_ratio, decay_steps,
                      warmup_updates, stair=False):
    """Linear warmup then (optionally staircased) exponential decay
    (parity: ``exponential_decay_schedule.py``)."""
    if stair:
        exponent = _floor(step / decay_steps)
    else:
        exponent = (step - warmup_updates) / float(decay_steps)
    decayed = base_lr * decay_ratio ** exponent
    if warmup_updates > 0:
        return _where(
            step <= warmup_updates, (step / float(warmup_updates)) * base_lr,
            decayed,
        )
    return decayed


def inverse_sqrt(step, *, base_lr, warmup_updates, warmup_init_lr):
    """Linear warmup then lr ~ 1/sqrt(step)
    (parity: ``inverse_square_root_schedule.py``)."""
    lr_step = (base_lr - warmup_init_lr) / warmup_updates
    decay_factor = base_lr * warmup_updates ** 0.5
    return _where(
        step < warmup_updates,
        warmup_init_lr + step * lr_step,
        decay_factor * (1e-30 + step) ** -0.5,
    )


def cosine(step, *, max_lr, min_lr, period, t_mult, shrink,
           warmup_updates, warmup_init_lr):
    """Warmup then cyclical cosine annealing (SGDR, arxiv 1608.03983;
    parity: ``cosine_lr_scheduler.py``).  ``t_mult`` grows each period;
    ``shrink`` scales both bounds per completed cycle."""
    t = step - warmup_updates
    # clamp to the cycle start: during warmup t is negative and the
    # annealing expression below is evaluated unconditionally (the warmup
    # select happens at the end), so a negative t would push the t_mult
    # log argument out of domain
    t = _where(t > 0, t, 0 * t)
    if t_mult != 1:
        i = _floor(_log(1 - t / period * (1 - t_mult)) / _log(t_mult))
        t_i = t_mult ** i * period
        t_curr = t - (1 - t_mult ** i) / (1 - t_mult) * period
    else:
        i = _floor(t / period)
        t_i = period
        t_curr = t - period * i
    cycle_shrink = shrink ** i
    lo, hi = min_lr * cycle_shrink, max_lr * cycle_shrink
    annealed = lo + 0.5 * (hi - lo) * (1 + _cos(math.pi * t_curr / t_i))
    if warmup_updates > 0:
        ramp = warmup_init_lr + step * (max_lr - warmup_init_lr) / warmup_updates
        return _where(step < warmup_updates, ramp, annealed)
    return annealed


def triangular(step, *, min_lr, max_lr, stepsize, shrink, shrink_min):
    """Cyclical triangular LR (CLR, arxiv 1506.01186; parity:
    ``triangular_lr_scheduler.py``)."""
    cycle = _floor(step / (2 * stepsize))
    cycle_shrink = shrink ** cycle
    hi = max_lr * cycle_shrink
    lo = min_lr * cycle_shrink if shrink_min else min_lr
    x = abs(step / stepsize - 2 * (cycle + 1) + 1)
    frac = _where(1 - x > 0, 1 - x, 0.0)
    return lo + (hi - lo) * frac


def _exp(x):
    if _traced(x):
        import jax.numpy as jnp

        return jnp.exp(x)
    return math.exp(x)


def tri_stage(step, *, init_lr, peak_lr, final_lr, warmup_steps, hold_steps,
              decay_steps, decay_factor):
    """Warmup -> hold -> exponential decay -> floor (SpecAugment, arxiv
    1904.08779; parity: ``tri_stage_lr_scheduler.py``).  Boundaries: the
    decay stage is inclusive of its last step."""
    ramp = (
        init_lr + (peak_lr - init_lr) * (step / warmup_steps)
        if warmup_steps > 0 else peak_lr
    )
    t_decay = step - warmup_steps - hold_steps
    decayed = peak_lr * _exp(-decay_factor * _where(t_decay > 0, t_decay, 0))
    out = _where(step <= warmup_steps + hold_steps + decay_steps,
                 decayed, final_lr)
    out = _where(step < warmup_steps + hold_steps, peak_lr, out)
    return _where(step < warmup_steps, ramp, out)


def fixed_warmup(step, *, base_lr, warmup_updates):
    """The per-update part of the ``fixed`` schedule: linear warmup onto
    the (epoch-driven) base LR (parity: ``fixed_schedule.py``)."""
    if warmup_updates > 0:
        return _where(
            step < warmup_updates,
            ((step + 1) / float(warmup_updates)) * base_lr,
            base_lr,
        )
    return base_lr
