"""Model step: seconds of JAX trace, lowering, compile and compile-cache
events inside the window's engine iterations (the engine's ``compile``
trace records), in ms per iteration; 0.0 in a window that compiles
nothing."""
import spanstats


def read(run):
    return spanstats.step_compile_ms(run)
