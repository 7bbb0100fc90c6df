"""compile: programs that had to be compiled during set-up because neither
JAX's persistent cache nor the program's AOT store held them (count)."""


def read(context):
    from benchmark.harness import counters

    return (counters.counter_total("dl4j_compile_cache_misses_total",
                                   source="persistent")
            + counters.counter_total("dl4j_compile_cache_misses_total",
                                     source="aot"))
