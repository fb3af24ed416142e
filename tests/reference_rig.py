"""A toy deployment of one ``model_type`` beside its plain reference
(``benchmark/reference/<model_type>.py``), for the tests that hold a serve
graph to its reference on logits: the seeded weights, the reference's full
forward pass, and the three ways a test drives the program (flat steps, the
tiled prefill scan through ``benchmark.check``, chained decode scans).

ONE built deployment per kernel mode and process (:meth:`Rig.deployment`,
``im.reset()`` between uses): a test starts its sequences at position 0 of a
slot, and a reset cache is what a fresh build holds.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import seeded_weights as sw
from flexflow_tpu.config import FFConfig
from flexflow_tpu.core.interpreter import init_params
from flexflow_tpu.model import FFModel
from flexflow_tpu.parallel.mesh import make_mesh
from flexflow_tpu.serve import BatchConfig
from flexflow_tpu.serve.inference_manager import InferenceManager
from flexflow_tpu.serve.models.base import ServeModelConfig, build_model


class Rig:
    def __init__(self, ref, hf, slots, cap, seq, seed, pad_to=64):
        self.ref, self.hf = ref, hf
        self.slots, self.cap, self.seq, self.seed = slots, cap, seq, seed
        self.pad_to = pad_to
        self._built = {}
        self._layers = {}

    # ---- the program ----------------------------------------------------
    def build(self, use_pallas=False, hf=None, **kw):
        hf = hf or self.hf
        ff = FFModel(FFConfig(),
                     mesh=make_mesh({"tp": 1}, jax.devices()[:1]))
        build_model(ff, ServeModelConfig.from_hf_config(hf), self.cap)
        return InferenceManager(
            ff, max_requests=self.slots, max_tokens_per_batch=self.cap,
            max_seq_len=self.seq, topk=hf["vocab_size"],
            use_pallas=use_pallas, **kw)

    def seeded(self, im, hf=None):
        """``im`` with the benchmark's seeded weights as its parameters (the
        library's own random draw, 4 s a build at toy widths, is never
        made: the tree's shapes are all the draw needs)."""
        hf = hf or self.hf
        like = jax.eval_shape(lambda: init_params(
            im.model.graph, im.plan, jax.random.PRNGKey(0), dtype=None))
        return im.init_operators_inference(params=sw.program_params(
            self.ref, hf, sw.base_key(self.seed), like, "float32"))

    def deployment(self, use_pallas=False):
        """THE deployment of this kernel mode, its caches reset."""
        if use_pallas not in self._built:
            self._built[use_pallas] = self.seeded(
                self.build(use_pallas=use_pallas))
        im = self._built[use_pallas]
        im.reset()
        return im

    # ---- the reference ----------------------------------------------------
    def reference_logprobs(self, ids, hf=None):
        """The reference's full forward pass of ``ids``: sorted
        log-probabilities at every position, and its greedy tokens."""
        hf, ref = hf or self.hf, self.ref
        key = sw.base_key(self.seed)
        g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, hf, "float32")
        padded = np.zeros(-(-len(ids) // self.pad_to) * self.pad_to,
                          np.int32)
        padded[:len(ids)] = ids
        x = ref.embed(hf, g, jnp.asarray(padded[None]))
        which = (len(padded), json.dumps(hf, sort_keys=True))
        if which not in self._layers:
            self._layers[which] = jax.jit(lambda key, i, x: ref.layer(
                hf, sw.draw_table(key, i, ref.LAYER, hf, "float32"), x))
        for i in range(ref.num_layers(hf)):
            x = self._layers[which](key, jnp.int32(i), x)
        logits = ref.head(hf, g, x[:, :len(ids)])[0]
        lp = jax.nn.log_softmax(logits, axis=-1)
        return (np.asarray(jnp.sort(lp, axis=-1)[:, ::-1]),
                np.asarray(jnp.argmax(logits, axis=-1)))

    def tokens(self, n, salt=0):
        rng = np.random.default_rng([self.seed, salt])
        return rng.integers(4, self.hf["vocab_size"], size=n).tolist()

    # ---- driving the program ---------------------------------------------
    def flat_step(self, im, pieces, seq_lens):
        """One flat step holding ``pieces`` = [(slot, ids, start position)];
        returns the sorted log-probabilities per piece, and the tokens."""
        toks, slots, pos = [], [], []
        for slot, ids, start in pieces:
            toks += list(ids)
            slots += [slot] * len(ids)
            pos += list(range(start, start + len(ids)))
            seq_lens[slot] = start + len(ids)
        bc = BatchConfig.build(toks, slots, pos, seq_lens,
                               max_tokens=im.max_tokens,
                               max_requests=im.max_requests)
        res = im.step(bc)
        lp, out, at = np.asarray(res.topk_logprobs), [], 0
        for _, ids, _ in pieces:
            out.append(lp[at:at + len(ids)])
            at += len(ids)
        return out, np.asarray(res.token_ids)

    def feed_flat(self, im, slot, ids, sizes, seq_lens):
        """``ids`` into ``slot`` from position 0 by flat steps of the given
        sizes (cycled); the log-probabilities at every position."""
        rows, at, i = [], 0, 0
        while at < len(ids):
            take = min(sizes[i % len(sizes)], len(ids) - at)
            (lp,), _ = self.flat_step(im, [(slot, ids[at:at + take], at)],
                                      seq_lens)
            rows.append(lp)
            at, i = at + take, i + 1
        return np.concatenate(rows)

    def decode_scan(self, im, slot, first, position, steps):
        """``steps`` decode steps of ``slot`` on the device, in chained scans
        of at most 32: the tokens produced after ``first`` (fed at
        ``position``)."""
        seq = np.zeros(im.max_requests, np.int32)
        seq[slot] = position + 1
        bc = BatchConfig.build([first], [slot], [position], seq,
                               max_tokens=im.max_tokens,
                               max_requests=im.max_requests)
        out, done = [], 0
        while done < steps:
            n = min(32, steps - done)
            allowed = np.zeros(im.max_tokens, np.int32)
            allowed[0] = steps - done
            toks, live, _, bc = im.decode_scan_async(
                bc, n, allowed=allowed, max_position=position + done)
            assert np.asarray(live)[:, 0].all()
            out += np.asarray(toks)[:, 0].tolist()
            done += n
        return out
