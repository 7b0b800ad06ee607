"""Per-layer metrics of set-up, read from the program's own stage account
(``observability/metrics.py``: ``stage_snapshot``).

The account books every second jax spends making a program to a stage
(``trace``, ``lower``, ``cache_load``: a backend compile the persistent
cache held, ``compile``: one it did not), to an owner (the innermost span
open on the thread: ``net_init``, ``forward``, ``host_dispatch``,
``device_step``, ``flops_derive``, ``opindex_lookup``, or ``none``) and to
the program, each second once. It is cumulative over the process and is
taken when the first of these readers runs. By then set-up is over and
nothing was made in the window (``window_compiles`` 0), so what stands
under the program's owners is set-up's. Two owners are left out and
printed in the notes instead: ``none`` holds the reference's programs and
the benchmark's own, and ``opindex_lookup`` is the traced run's own cost,
paid after the window.

A reader returns None when the program keeps no such account (a commit
before it did), and the line leaves the metric out.
"""

from __future__ import annotations

LEFT_OUT = ("none", "opindex_lookup")


def _account(m):
    """This run's digest of the program's account (``_digest``), taken
    from the program by the first reader that asks and kept under
    ``m.notes["setup_account"]``, where every run prints it; None when
    the program keeps no account."""
    digest = m.notes.get("setup_account")
    if digest is None:
        try:
            from deeplearning4j_tpu.observability import metrics as obs
            account, largest = obs.stage_snapshot(), obs.largest_programs
        except (ImportError, AttributeError):
            return None
        digest = m.notes["setup_account"] = _digest(
            account, largest(10_000))
        # window_compiles is a count; the stage spans in the window say
        # of what
        digest["made_in_window"] = [
            {"span": s.name, "program": (s.attrs or {}).get("program"),
             "parent": getattr(s, "parent", None),
             "seconds": s.dur_us * 1e-6}
            for s in m.spans if s.name.startswith("xla_")]
    return digest


def _digest(account: dict, by_program: list, largest: int = 8) -> dict:
    def by_owner(table):
        out: dict = {}
        for stage, owners in table.items():
            for owner, value in owners.items():
                out.setdefault(owner, {})[stage] = value
        return out

    seconds = by_owner(account["seconds"])
    programs = by_owner(account["programs"])
    return {
        "seconds_by_owner": {o: v for o, v in seconds.items()
                             if o not in LEFT_OUT},
        "programs_by_owner": {o: v for o, v in programs.items()
                              if o not in LEFT_OUT},
        "left_out": {o: {"seconds": seconds.get(o, {}),
                         "programs": programs.get(o, {})}
                     for o in LEFT_OUT},
        "spans": account["spans"],
        "largest_programs": [p for p in by_program
                             if p["owner"] not in LEFT_OUT][:largest],
    }


def _under_program_owners(m, table: str, stage: str):
    account = _account(m)
    if account is None:
        return None
    return sum(stages.get(stage, 0) for stages in account[table].values())


def stage_seconds(m, stage: str):
    """Seconds of ``stage`` under the program's owners; 0.0 where the
    account saw none (``compile`` on a warm run)."""
    return _under_program_owners(m, "seconds_by_owner", stage)


def stage_programs(m, stage: str):
    """Outermost intervals of ``stage`` under the program's owners: for
    ``lower`` the programs set-up made."""
    return _under_program_owners(m, "programs_by_owner", stage)


def span_seconds(m, span: str):
    """Summed duration of the set-up span ``span`` (``net_init``,
    ``flops_derive``): the stage seconds booked to it as owner, and its
    own Python besides. 0.0 where the run never opened it."""
    account = _account(m)
    if account is None:
        return None
    return account["spans"].get(span, {}).get("seconds", 0.0)
