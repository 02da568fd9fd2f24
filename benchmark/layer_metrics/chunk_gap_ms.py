"""Mean device-idle gap between the loop's programs, per turn of the loop (a
chunk, or an iteration where the trainer dispatches each on its own): the
host's window less the time any compiled program ran in the trace, over the
turns the window held."""


def read(facts):
    t = facts["trace"]
    if not t or not facts["window_chunks"] or not facts["window_s"]:
        return None
    return 1000.0 * (facts["window_s"] - t["programs_s"]) / facts["window_chunks"]
