"""The account's own check (%), untraced: the loop's time that NO phase
covers (the self time of ``engine.pass`` / ``.decode`` /
``.prefill_chunk`` and the loop between passes) over the wall time of
the intervals read less ``parked`` (``chipbench/loop_account.py``).
The four metrics beside it say where the host's time goes only as far
as this stays small."""

from chipbench import loop_account


def read(obs):
    acct = loop_account.read(obs)
    if acct is None:
        return None
    busy = acct["wall_ns"] - acct["ns"]["parked"]
    return 100.0 * acct["ns"]["unaccounted"] / busy if busy else None
