"""Process start to the start of the window: import, the application's own
run() (realize, init, compile or cache load), seeding, the first-chunk
check's device part and the warm-up. The reference is computed after the
window and is not in it."""


def read(ctx):
    return ctx["window"]["setup_s"]
