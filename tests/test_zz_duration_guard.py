"""Tier-1 duration guard (ISSUE 16 satellite).

The ``zz`` filename sorts this module last, so by the time it runs the
conftest ``pytest_runtest_logreport`` hook has timed every other test
in the session.  Any NON-``slow`` test whose call phase crossed the
``DURATION_BUDGET_S`` budget (20 s) fails HERE, by name — the fix is
either to make the test cheaper or to move it behind
``@pytest.mark.slow`` where its cost is a visible, budgeted decision.

On partial runs (``pytest tests/test_foo.py``) only the selected tests
were timed — the guard still holds for exactly what ran.  The driver's run
(six xdist workers, ``--dist loadfile``, ``timeout 1470``, about 300 s) puts
this module on one worker, which has timed only the files it ran: there the
guard holds for that worker's share.
"""
import conftest


def test_no_unmarked_test_exceeds_duration_budget():
    offenders = sorted(conftest.DURATION_OFFENDERS,
                       key=lambda p: -p[1])
    assert not offenders, (
        f"non-slow test(s) exceeded the {conftest.DURATION_BUDGET_S:.0f}s "
        f"tier-1 budget: "
        + ", ".join(f"{nid} ({s}s)" for nid, s in offenders)
        + " — speed them up or mark them @pytest.mark.slow")
