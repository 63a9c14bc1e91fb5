"""The serving cell's control at a size a test run holds: the reference
computed in bfloat16, one precision below the configuration's float32,
put in the program's place on the same prompts and served tokens, must
read above the cell's limit, while the program reads below it."""

import smoke
import bench


def test_bf16_control_fails_the_limit():
    sv = smoke.serve_kind()
    limits = bench.load_json(smoke.HERE / "cells"
                             / "granite-w1a1.chat.json")["limits"]
    cell = smoke.cell(limits, check_requests=4)
    eng, dims = sv.build(cell, 2 ** 32 + 17, lambda msg: None)
    _, _, reqs, served = sv.serve(eng, cell, 2 ** 32 + 17, 1.5)
    _, gaps, cut, control = sv.check(cell, 2 ** 32 + 17, dims, reqs, served,
                                     control=True)
    limit = limits["served_logit_gap"]
    assert cut == [len(g) for g in gaps]
    full = [len(c) for c in control]
    assert sv.compared(gaps, cut) <= limit < sv.compared(control, full)
