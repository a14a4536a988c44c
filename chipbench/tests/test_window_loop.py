from chipbench.drivers.train_loop import window_loop


class StubBooster:
    """A tree takes 3 s of the fake clock; the final force takes 2 s."""

    def __init__(self):
        self.now, self.updates, self.forces = 0.0, 0, 0

    def clock(self):
        return self.now

    def update(self):
        self.updates += 1
        self.now += 3.0

    def force(self):
        self.forces += 1
        self.now += 2.0


def test_whole_trees_and_the_final_force_are_counted():
    b = StubBooster()
    win = window_loop(b.update, b.force, seconds=10.0, min_trees=1, clock=b.clock)
    # trees at 3, 6, 9 s do not pass 10 s; the fourth ends at 12 s; none is started after
    assert win["trees"] == 4 and b.updates == 4
    assert b.forces == 1
    assert win["seconds"] == 14.0            # 4 x 3 s + the force's 2 s
    assert win["update_returned_s"] == [3.0, 6.0, 9.0, 12.0]


def test_the_least_number_of_trees_is_grown_in_a_short_window():
    b = StubBooster()
    win = window_loop(b.update, b.force, seconds=1.0, min_trees=4, clock=b.clock)
    assert win["trees"] == 4 and win["seconds"] == 14.0


def test_traced_first_trees_count_as_window_trees():
    b = StubBooster()

    def first(update):
        update()
        update()
        return 2

    win = window_loop(b.update, b.force, seconds=13.0, min_trees=1, clock=b.clock,
                      first_trees=first)
    # 2 traced trees (6 s), then trees until 13 s is passed: 5 trees, 15 s, and the force
    assert win["trees"] == b.updates == 5
    assert b.forces == 1 and win["seconds"] == 17.0
