"""Configuration kind ``dense_platform``: the paper's RISC-V + CIM platform
running one dense layer (Table III) per job.

The traffic file gives the layer (``h``, ``w``, ``p``) and its ``mode``:
``cim`` offloads it to the CIM units, one manager CPU per segment, ``riscv``
runs the nested-loop VMM on the CPU next to DRAM.  Everything that decides
what is simulated lives here: the seeded layer data, the DRAM image and its
addresses, the assembly the simulated RISC-V cores run.  The program is
entered only through ``segmentation.build``; the platform's memory map
(``repro.vp.isa``) is the simulated hardware's specification, so its
addresses are read from there.

A job is correct when its output words equal A @ B and it took the rounds
and cycles the traffic file states: a dense layer's simulated time does not
depend on its data.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import check, jobs, reference
from repro.core import segmentation as sg
from repro.vp import isa

A_BASE_W = 1024   # DRAM word offset of A; B and O follow it
B_STAGE = 1024    # manager scratch word offset of its staged input vectors
OUT0 = 256        # scratch word offset of mailbox ordinal 0's output area

# number -> limit; the order is the order they are printed in
LIMITS = {"failed_jobs": 0, "output_words_wrong": 0,
          "jobs_rounds_off": 0, "jobs_cycles_off": 0}


def platform_descs(config: dict):
    """Segment descriptors of a dense platform configuration."""
    if config["segmentation"] != "uniform":
        raise ValueError(f"unknown segmentation {config['segmentation']!r}")
    return sg.uniform(config["n_cpus"], config["cims_per_cpu"])


def riscv_program(h, w, p, a_base, b_base, o_base) -> str:
    """The paper's nested-loop VMM on RISC-V + main memory:
    O[h, p] = A[h, w] @ B[w, p], word-addressed int32, row-major."""
    return f"""
    li s0, 0
outer_i:
    li s1, 0
outer_j:
    li t0, 0
    li s2, 0
    li t4, {w * 4}
    mul t2, s0, t4
    li t4, {a_base}
    add t2, t2, t4
    add t3, s1, s1
    add t3, t3, t3
    li t4, {b_base}
    add t3, t3, t4
inner_k:
    lw t4, 0(t2)
    lw t5, 0(t3)
    mul t6, t4, t5
    add t0, t0, t6
    addi t2, t2, 4
    addi t3, t3, {4 * p}
    addi s2, s2, 1
    li t4, {w}
    blt s2, t4, inner_k
    li t4, {p * 4}
    mul t1, s0, t4
    add t5, s1, s1
    add t5, t5, t5
    add t1, t1, t5
    li t4, {o_base}
    add t1, t1, t4
    sw t0, 0(t1)
    addi s1, s1, 1
    li t4, {p}
    blt s1, t4, outer_j
    addi s0, s0, 1
    li t4, {h}
    blt s0, t4, outer_i
    halt
"""


def cim_pair_program(cim_bases, h, w, p_lo, p_hi, o_base, p_total, ordinals,
                     in_res=8, out_res=8) -> str:
    """A manager CPU driving two CIM units over vectors [p_lo, p_hi) in a
    software pipeline: vector j to unit 0, vector j+1 to unit 1, then poll
    and drain both.  Outputs go to O[:, j] in shared DRAM."""
    cfg = ((h & 0x1FF) | (w & 0x1FF) << 9 | (in_res & 0xF) << 18
           | (out_res & 0xF) << 22)
    sb = isa.SCRATCH_BASE
    bs = sb + B_STAGE * 4
    f0, f1 = ordinals[0] * 4, ordinals[1] * 4
    out0 = (OUT0 + ordinals[0] * 256) * 4
    out1 = (OUT0 + ordinals[1] * 256) * 4

    def stream(unit_reg, vec_reg, label, start_reg):
        return [
            f"    mul t3, {vec_reg}, t2", "    add t3, t3, t3",
            "    add t3, t3, t3", f"    li t5, {bs}", "    add t3, t3, t5",
            "    li t4, 0", f"{label}:", "    lw t1, 0(t3)",
            f"    sw t1, {isa.CIM_REG_INPUT}({unit_reg})",
            "    addi t3, t3, 4",
            "    addi t4, t4, 1", f"    blt t4, t2, {label}",
            f"    sw zero, {isa.CIM_REG_START}({start_reg})",
        ]

    def drain(flag, poll, area, vec_reg, label):
        return [
            f"    li t0, {sb}", f"{poll}:", f"    lw t1, {flag}(t0)",
            f"    beq t1, zero, {poll}", f"    li t3, {sb + area}",
            f"    addi t5, {vec_reg}, {p_lo}", "    add t5, t5, t5",
            "    add t5, t5, t5", f"    li t1, {o_base}", "    add t5, t5, t1",
            "    li t4, 0", f"    li t2, {h}", f"{label}:", "    lw t1, 0(t3)",
            "    sw t1, 0(t5)", "    addi t3, t3, 4",
            f"    addi t5, t5, {4 * p_total}", "    addi t4, t4, 1",
            f"    blt t4, t2, {label}",
        ]

    src = [
        f"    li s0, {cim_bases[0]}", f"    li s1, {cim_bases[1]}",
        f"    li t0, {cfg}", f"    sw t0, {isa.CIM_REG_CONFIG}(s0)",
        f"    sw t0, {isa.CIM_REG_CONFIG}(s1)", "    li s2, 0",
        f"    li s3, {p_hi - p_lo}", "pair_loop:", f"    li t0, {sb}",
        f"    sw zero, {f0}(t0)", f"    sw zero, {f1}(t0)", f"    li t2, {w}",
        *stream("s0", "s2", "in0", "s0"),
        "    addi t6, s2, 1", "    bge t6, s3, drain0",
        *stream("s1", "t6", "in1", "s1"),
        "drain0:",
        *drain(f0, "poll0", out0, "s2", "out0"),
        "    addi t6, s2, 1", "    bge t6, s3, next_pair",
        *drain(f1, "poll1", out1, "t6", "out1"),
        "next_pair:", "    addi s2, s2, 2", f"    li t2, {w}",
        "    blt s2, s3, pair_loop", "    halt",
    ]
    return "\n".join(src)


@dataclasses.dataclass
class DenseJob:
    a: np.ndarray          # (h, w) int32, also the crossbar image
    b: np.ndarray          # (w, p) int32 input vectors
    o_word: int            # DRAM word offset of O
    build_kw: dict         # keyword arguments of segmentation.build


def job(config: dict, traffic: dict, seed: int, index: int,
        t_steps=None) -> DenseJob:
    """One Table III layer job: seeded A and B, DRAM image, programs.
    A dense layer has no presentation length; ``t_steps`` is ignored."""
    h, w, p = (traffic["layer"][k] for k in ("h", "w", "p"))
    rng = jobs.rng_for(seed, index)
    a = rng.integers(-8, 8, (h, w)).astype(np.int32)
    b = rng.integers(-8, 8, (w, p)).astype(np.int32)
    b_w = A_BASE_W + h * w
    o_w = b_w + w * p
    words = np.zeros(o_w + h * p, np.int32)
    words[A_BASE_W:b_w] = a.reshape(-1)
    words[b_w:o_w] = b.reshape(-1)
    a_b, b_b, o_b = A_BASE_W * 4, b_w * 4, o_w * 4
    if traffic["mode"] == "riscv":
        kw = dict(programs={0: riscv_program(h, w, p, a_b, b_b, o_b)},
                  dram_words=words)
        return DenseJob(a, b, o_w, kw)
    if traffic["mode"] != "cim":
        raise ValueError(f"unknown dense mode {traffic['mode']!r}")
    descs = platform_descs(config)
    managed = {}  # manager segment -> its units, in global id order
    for s, d in enumerate(descs):
        for _ in range(d.n_cims):
            managed.setdefault(d.cim_mgr if d.cim_mgr >= 0 else s, []).append(
                sum(len(v) for v in managed.values()))
    mgrs = traffic["managers"]
    per = -(-p // len(mgrs))
    programs, crossbars, scratch = {}, {}, {}
    for mi, m in enumerate(mgrs):
        p_lo, p_hi = mi * per, min((mi + 1) * per, p)
        if p_lo >= p_hi:
            continue
        units = managed[m][:2]
        ords = [managed[m].index(g) for g in units]
        for g in units:
            crossbars[g] = a.astype(np.int8)
        bases = [isa.CIM_BASE + g * isa.CIM_STRIDE for g in units]
        programs[m] = cim_pair_program(bases, h, w, p_lo, p_hi, o_b, p, ords)
        staged = np.ascontiguousarray(b[:, p_lo:p_hi].T).reshape(-1)
        scratch[m] = {B_STAGE: staged.astype(np.int32)}
    kw = dict(programs=programs, dram_words=words, crossbars=crossbars,
              scratch_init=scratch)
    return DenseJob(a, b, o_w, kw)


def _build(config: dict, traffic: dict, job: DenseJob):
    return sg.build(platform_descs(config),
                    channel_latency=config["channel_latency"],
                    local_latency=config["local_latency"],
                    use_kernel=traffic["use_kernel"], **job.build_kw)


def build(config: dict, traffic: dict, job: DenseJob):
    """Placed (cfg, states, pending, meta) of one dense job on the
    configured platform; a dense job has no meta."""
    return (*jobs.placed(_build, config, traffic, job), None)


def fetch(states, job: DenseJob, cfg):
    """The output words O and the per-segment simulated time."""
    h, p = job.a.shape[0], job.b.shape[1]
    return (states["dram"]["data"][cfg.dram_segment,
                                   job.o_word:job.o_word + h * p],
            states["time"])


def compare(config: dict, traffic: dict, entries, done):
    """(values, failed jobs): every job of ``done`` against A @ B and the
    traffic file's rounds and cycles; ``entries`` are the pool's
    (job, meta) pairs."""
    vals = dict.fromkeys(LIMITS, 0)
    wants, failed = {}, 0
    for d in done:
        if d.out is None:
            vals["failed_jobs"] += 1
            failed += 1
            continue
        job = entries[d.entry][0]
        if d.entry not in wants:
            wants[d.entry] = reference.dense_output(job.a, job.b)
        wrong = check.dense_wrong(job, d.out[0], want=wants[d.entry])
        r_off = d.rounds != traffic["expect_rounds"]
        c_off = d.cycles != traffic["expect_cycles"]
        vals["output_words_wrong"] += wrong
        vals["jobs_rounds_off"] += r_off
        vals["jobs_cycles_off"] += c_off
        failed += bool(wrong or r_off or c_off)
    return vals, failed


def control(config: dict, traffic: dict, seed: int) -> dict:
    """The bfloat16 reference's output words against the exact one."""
    job_ = job(config, traffic, seed, 0)
    got = reference.dense_output(job_.a, job_.b, "bf16")
    return {"output_words_wrong": check.dense_wrong(job_, got)}
