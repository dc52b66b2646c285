"""A served path added as a file alone: ``user_bags`` with its output
multiplied by the configuration's ``output_scale``, and a check that
divides the output by ``check_scale`` before ``user_bags``' comparison
with the reference. Equal scales make a correct run; unequal ones a wrong
answer that the check has to catch."""
import run

base = run.served_module("user_bags")
inputs, shape, step_work = base.inputs, base.shape, base.step_work
counters, attach = base.counters, base.attach


def build(cfg, seed):
    engine = base.build(cfg, seed)
    engine.output_scale = cfg["output_scale"]
    return engine


def serve(engine, x):
    output, reads = base.serve(engine, x)
    return output * engine.output_scale, reads


def check(cfg, tr, seed, served, prog_reads, sample):
    unscaled = [(k, output / cfg["check_scale"]) for k, output in sample]
    return base.check(cfg, tr, seed, served, prog_reads, unscaled)
