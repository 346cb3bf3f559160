"""Open-loop request schedules for serving cells, from a traffic file and
``--seed``.

The schedule (arrival times and output lengths) is drawn from the traffic
file's own ``schedule_seed``, so every run of a cell offers the same load;
``--seed`` draws the prompts' token ids (and, elsewhere, the weights).

- Arrivals: a Poisson process at ``rate_per_s`` over the window.
- Prompts: ``prompt_len`` tokens each, from the traffic file's ``domains``
  (one domain a prompt, chosen evenly).
- Outputs: ``max_new_tokens`` from a log-normal of median
  ``output_len.median`` and shape ``output_len.sigma``, clipped to
  ``[output_len.min, output_len.max]``; requests run to that length.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench.corpus import Domains


def schedule(traffic: Dict, seconds: float) -> List[Tuple[float, int]]:
    """``(due_s, max_new_tokens)`` for every arrival in ``[0, seconds)``."""
    rng = np.random.default_rng(traffic["schedule_seed"])
    out, t = [], 0.0
    o = traffic["output_len"]
    while True:
        t += rng.exponential(1.0 / traffic["rate_per_s"])
        if t >= seconds:
            return out
        n = int(round(o["median"] * np.exp(o["sigma"] * rng.standard_normal())))
        out.append((t, int(np.clip(n, o["min"], o["max"]))))


def prompts(traffic: Dict, vocab_size: int, seed: int,
            n: int) -> List[np.ndarray]:
    dom = Domains(traffic["domains"], vocab_size)
    out = []
    for i in range(n):
        rng = np.random.default_rng(np.random.SeedSequence([seed, i]))
        out.append(dom.row(rng, int(rng.integers(len(dom))),
                           traffic["prompt_len"]))
    return out
