"""The control: the reference in fp8 (e4m3), the precision below the
configuration's bfloat16, put in the program's place, reads above the
limit that the program's own tokens stay under."""
from bench import cell, check
from bench.tests import tiny

SEED = 4242


def test_fp8_control_fails_where_the_program_passes():
    c = tiny.load()
    served = cell.Served(c, SEED)
    try:
        served.warm()
        d = served.drive(SEED, 2.0)
    finally:
        served.close()
    m = served.dims
    got, ctl = cell.reference_gaps(m, SEED, d, control=True)
    limit = c.config["limits"]["logit_gap"]
    program, control = cell.widest(got), cell.widest(ctl)
    checks = check.exact_checks(d.records, d.plan, SEED, m.vocab,
                                served.admitted)
    assert check.verdict(dict(checks, logit_gap={"value": program,
                                                 "limit": limit}))
    assert not check.verdict(dict(checks, logit_gap={"value": control,
                                                     "limit": limit}))
