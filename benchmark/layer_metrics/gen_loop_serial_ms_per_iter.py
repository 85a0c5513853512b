"""What the generation engine's loop costs an iteration outside its two awaits
of the device, in ms, over the whole window: `gen_loop_seconds_total` of the
phases `sweep`, `admit`, `account`, `emit` and `retire` over
`gen_iterations_total`, from the two scrapes. All of it passes with nothing
queued on the chip (the loop hands the device one thing at a time). The note
gives every phase, and the eight's sum, which is the window's length."""

from benchmark import gen_loop, gen_window


def read(run: dict):
    by_phase = gen_loop.loop_seconds(run)
    iters = gen_window.total(run, "gen_iterations_total")
    if by_phase is None or iters <= 0:
        return None
    run.setdefault("notes", []).append(
        f"gen_loop_serial_ms_per_iter: {iters:.0f} iterations; ms an iteration by phase: "
        + ", ".join(f"{p}={1e3 * s / iters:.3f}" for p, s in by_phase.items())
        + f"; the eight phases sum to {sum(by_phase.values()):.3f} s")
    return 1e3 * sum(by_phase[p] for p in gen_loop.SERIAL_PHASES) / iters
