"""l2_host_gb: host memory of the compiled step, in GB: its host temp,
argument and output bytes less what aliases, from the compiler's memory
analysis of the program the window runs."""


def read(ctx):
    m = ctx["memory"]
    host = (m.host_argument_size_in_bytes + m.host_output_size_in_bytes
            + m.host_temp_size_in_bytes - m.host_alias_size_in_bytes)
    return host / 1e9 if host > 0 else None
