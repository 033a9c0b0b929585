"""Architecture families of the benchmark's cross-encoders, one module each.

A configuration file names its family by its ``reference`` key: the
family ``families/<reference>.py`` maps the file onto the program, and the
plain reference ``references/<reference>.py`` computes what the program
should.  The harness (``cell.py``, ``run.py``, ``check.py``) reaches every
architecture-specific part through these hooks:

- ``program_config(cfg)``: the program's model configuration;
- ``init_leaf(path, key, shape, dtype, cfg)``: one weight leaf, by its path
  in the program's parameter tree, from its own key;
- ``scorer_kwargs(cfg)``: ``DeviceCEScorer``'s keyword arguments beyond the
  weights, the configuration and the token tables;
- ``flops_per_pair(cfg)``: model FLOPs of one pair's forward, for ``mfu``;
- ``attention(cfg)``: the published attention's shape (``layers``,
  ``heads``, ``qk_head_dim``, ``v_head_dim``, ``seq_len``, ``causal``),
  for the attention kernel's roofline;
- ``TINY``: the cut that the benchmark's CPU tests merge into a
  configuration of this family.

Families may import the program's configuration classes; references
import nothing of the program.
"""

HOOKS = ("program_config", "init_leaf", "scorer_kwargs", "flops_per_pair",
         "attention", "TINY")
