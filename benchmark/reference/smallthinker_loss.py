"""The update step for one solo-layout window of the token game, in plain
float32 over the plain forward (``smallthinker.forward``): the loss that
``handyrl_tpu/ops/losses.py`` ``compute_loss`` composes for a sequence net
with both targets V-trace and no per-ply reward, and a written-out first
Adam step. The loss of the outputs, its targets and the Adam step name no
net and are ``trinity_mini_loss``'s; the router has no rule to follow after
the step (the source's config has none): it stays as seeded.
"""

from . import smallthinker
from .trinity_mini_loss import (ADAM_B1, expert_counts,  # noqa: F401
                                first_adam_step, loss_of_outputs, targets)


def loss(variables, window, value_target, advantage, cfg, entropy_coef,
         entropy_decay, **forward_args):
    """The loss from the parameters: what ``jax.vjp`` differentiates."""
    out = smallthinker.forward(variables, window['ids'],
                               window['first_position'], window['valid'] > 0,
                               cfg, **forward_args)
    return loss_of_outputs(out, window, value_target, advantage,
                           entropy_coef, entropy_decay)
