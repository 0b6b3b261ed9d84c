"""Word2Vec over a text file (or a built-in demo corpus).

Mirrors the reference's Word2Vec example: sentence iterator →
tokenizer → builder → fit → nearest-word queries → save vectors.

Run: python examples/word2vec_text.py [--input corpus.txt]
"""

import os
import sys

# allow running straight from a repo checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import argparse

from deeplearning4j_tpu.nlp import Word2Vec
from deeplearning4j_tpu.nlp.serializer import write_word_vectors
from deeplearning4j_tpu.nlp.tokenization import (CommonPreprocessor,
                                                 DefaultTokenizerFactory,
                                                 FileSentenceIterator,
                                                 ListSentenceIterator)

DEMO = [
    "the king rules the kingdom with the queen",
    "the queen advises the king on royal matters",
    "the cat chases the mouse through the house",
    "the mouse hides from the cat in the house",
    "the king and queen host a royal feast",
    "a cat and a mouse live in the old house",
] * 50


def main(path=None, out="/tmp/vectors.txt"):
    it = FileSentenceIterator(path) if path else ListSentenceIterator(DEMO)
    tf = DefaultTokenizerFactory()
    tf.set_token_pre_processor(CommonPreprocessor())
    w2v = (Word2Vec.builder()
           .layer_size(64)
           .window_size(5)
           .min_word_frequency(3)
           .negative_sample(5)
           .epochs(5)
           .sampling(0.0)
           .seed(42)
           .iterate(it)
           .tokenizer_factory(tf)
           .build())
    w2v.fit()
    print(f"vocab: {len(w2v.vocab)} words")
    for word in ("king", "cat"):
        if w2v.get_word_vector(word) is not None:
            print(f"nearest({word}):", w2v.words_nearest(word, 4))
    write_word_vectors(w2v, out)
    print(f"vectors written to {out}")


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--input", default=None)
    args = p.parse_args()
    main(args.input)
