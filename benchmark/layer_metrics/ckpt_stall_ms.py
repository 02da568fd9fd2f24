"""Mean wall of the program's span ``train.fetch.checkpoint`` in the window:
materialising the trees and writing them, with the device idle."""


def read(facts):
    walls = [dur for path, _, dur in facts["spans"] if path.endswith("train.fetch.checkpoint")]
    if not walls:
        return None
    return 1000.0 * sum(walls) / len(walls)
