"""Prefill pieces that resumed a stored recurrent state, over all pieces, in
percent: `ssm_pieces_total{start=carried}` over both values of `start`. A
piece that starts its prompt begins from zeros; every later piece of the same
prompt reads what the slot holds. Neither is better in itself (the traffic's
prompt lengths decide it: a prompt of one launch carries nothing); `higher` is
declared because a fall at fixed traffic means prompts were cut into fewer,
fuller pieces OR that carried pieces are being lost, and the second is a
fault, so a fall is what to look at."""

from benchmark import gen_window


def read(run: dict):
    zero = gen_window.total(run, "ssm_pieces_total", start="zero")
    carried = gen_window.total(run, "ssm_pieces_total", start="carried")
    if zero + carried <= 0:
        return None
    return 100.0 * carried / (zero + carried)
