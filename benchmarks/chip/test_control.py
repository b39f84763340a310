"""The control of ``correct``: the plain reference one precision step
below the configuration's (float32 "high" for "highest"; 4-bit for
W8A8) put in the program's place must put more of its requests off by
over ``request_gap`` than the configuration's ``off_share`` allows, at
the configuration's own widths, on every seed tried, against every
answer the reference accepts.  The
program's side (sound runs pass, broken ones fail) is in
``test_faults.py``; the same readings on the chip come from
``control.py``."""
import numpy as np
import pytest

import harness


@pytest.mark.parametrize("config", ["pointmlp-elite", "pointmlp-lite"])
@pytest.mark.parametrize("seed", [7, 2 ** 31 + 5])
def test_control_fails_the_limit(config, seed):
    c = harness.load_config(config)
    pts = harness.make_pool(c, harness.prng_key(seed, harness.SALT_CLOUDS),
                            16)
    own = harness.reference_forward(c, seed, pts, None, seed)
    more = harness.open_decision_answers(c, seed, pts, pts, seed)
    accepted = [np.concatenate([a[None], b]) for a, b in zip(own, more)]
    low = {"bits": 4} if c["precision"] == "int8" else {"mode": "high"}
    got = harness.reference_forward(c, seed, pts, None, seed, **low)
    gaps = harness.request_gaps(got, accepted)
    lim = c["limits"]
    assert harness.off_share(gaps, lim["request_gap"]) > lim["off_share"]
