"""Layer: device.  XLA's own account of the cell's largest compiled
program on one chip: arguments + temporaries + outputs - aliased
(``memory_analysis()``; the allocator's peak leaves temporaries out on
this runtime, PERF.md PR 21)."""


def read(run):
    sizes = []
    for compiled in run.programs.values():
        m = compiled.memory_analysis()
        sizes.append(m.argument_size_in_bytes + m.temp_size_in_bytes
                     + m.output_size_in_bytes - m.alias_size_in_bytes)
    return max(sizes) / 2 ** 30 if sizes else None
