"""Prompt tokens served from the radix prefix cache over prompt tokens
submitted: ``prefix_tokens_reused_total`` of ``Engine.stats()`` and the
prompt lengths the benchmark submitted, both as differences across the
window."""


def read(record, trace):
    if not record["prompt_tokens_submitted"]:
        return None
    return (100.0 * record["prefix_tokens_reused"]
            / record["prompt_tokens_submitted"])
